"""End-to-end training launcher (CPU-scale runs + the production recipe).

``python -m repro.launch.train --arch llama3-8b --smoke --steps 50`` trains
the reduced config on local devices; on a pod the same script runs the full
config on the production mesh with checkpoint/restart and straggler
monitoring wired in.

``--chaos`` switches to the **chaos-tested elastic** harness
(:func:`run_chaos`): N training steps on the local (8-fake-device) mesh
while a deterministic fault injector (``repro.ft.chaos``) kills and
straggles simulated hosts on a virtual clock.  A detected loss triggers the
restart state machine — RestartPolicy backoff, ``plan_rescale`` onto the
survivors, sharding rules re-derived from the logical table
(``ft.rescale_rules``), cross-mesh checkpoint restore, and bit-identical
``(seed, step)`` batch replay from the data pipeline's cursor.  See
``docs/RESILIENCE.md`` and ``repro.testing.check_chaos``.
"""
from __future__ import annotations

import argparse
import dataclasses
import tempfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import substrate
from repro.checkpoint import CheckpointManager, restore_checkpoint
from repro.checkpoint.ckpt import latest_step, tear_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, Pipeline, make_pipeline
from repro.launch import compile_cache
from repro.ft import (ChaosSchedule, FaultInjector, HeartbeatMonitor,
                      RestartPolicy, StragglerMitigator, plan_rescale,
                      rescale_rules)
from repro.models import lm
from repro.parallel.sharding import (abstract_params, default_rules,
                                     init_params, param_shardings)
from repro.testing.timing import now
from repro.train import (OptConfig, TrainState, abstract_train_state,
                         make_train_step, train_state_shardings)
from repro.train.optimizer import adamw_init


def run(arch: str, *, smoke: bool = True, steps: int = 50,
        global_batch: int = 8, seq_len: int = 64, lr: float = 3e-3,
        ckpt_dir: str | None = None, ckpt_every: int = 25,
        n_microbatches: int = 1, resume: bool = True, log_every: int = 10,
        seed: int = 0) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    rules = default_rules(None)          # single-process CPU run
    opt_cfg = OptConfig(lr=lr, warmup_steps=max(2, steps // 10),
                        total_steps=steps)

    key = jax.random.key(seed)
    params = init_params(lm.model_defs(cfg), key)
    state = TrainState(params, adamw_init(params, opt_cfg))

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)
    start_step = 0
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    if mgr and resume and latest_step(ckpt_dir) is not None:
        state, start_step, _ = restore_checkpoint(ckpt_dir, state)
        start_step = int(start_step)
        print(f"[train] resumed from step {start_step}")

    pipe = make_pipeline(dcfg, start_step=start_step)
    step_fn = jax.jit(make_train_step(cfg, rules, opt_cfg,
                                      n_microbatches=n_microbatches))

    monitor = HeartbeatMonitor(n_hosts=1)
    straggler = StragglerMitigator()
    losses = []
    t_prev = now()
    for step in range(start_step, steps):
        tokens = jnp.asarray(next(pipe))
        batch = {"tokens": tokens}
        if cfg.family in ("encdec", "vlm"):
            rng = np.random.default_rng(step)
            T = lm.context_len(cfg, seq_len)
            batch["ctx"] = jnp.asarray(
                rng.normal(size=(global_batch, T, cfg.d_ctx)) * 0.1,
                jnp.float32)
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = now() - t_prev
        t_prev = now()
        monitor.beat(0, step, dt)
        straggler.update({0: monitor.hosts[0].ewma_step_s})
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} ({dt*1e3:.0f} ms)",
                  flush=True)
        if mgr and (step + 1) % ckpt_every == 0:
            mgr.save_async(state, step + 1)
    if mgr:
        mgr.wait()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "start_step": start_step}


# ---------------------------------------------------------------------------
# Chaos-tested elastic training
# ---------------------------------------------------------------------------

def _fingerprint(batch: np.ndarray) -> int:
    """Byte-exact batch identity: the replay assertion currency."""
    return zlib.crc32(np.ascontiguousarray(batch).tobytes())


def _host_mesh(devices, dp: int, model: int):
    return substrate.make_mesh((dp, model), ("data", "model"),
                               devices=devices)


def _place_state(cfg, opt_cfg, seed: int, rules) -> TrainState:
    """Deterministic init (pure function of ``seed``) placed under
    ``rules`` — fresh starts and post-rescale cold starts are identical."""
    key = jax.random.key(seed)
    params = init_params(lm.model_defs(cfg), key)
    state = TrainState(params, adamw_init(params, opt_cfg))
    if rules.mesh is not None:
        state = jax.device_put(state,
                               train_state_shardings(cfg, opt_cfg, rules))
    return state


def run_chaos(arch: str = "llama3-8b", *, steps: int = 12,
              chaos_seed: int = 0, chaos_spec: str | None = None,
              n_hosts: int = 2, model_axis: int = 2, global_batch: int = 8,
              seq_len: int = 32, lr: float = 3e-3, seed: int = 0,
              ckpt_dir: str | None = None, ckpt_every: int = 2,
              timeout_s: float = 3.5, base_step_s: float = 1.0,
              max_restarts: int = 3, backoff_s: float = 1.0,
              n_microbatches: int = 1, log_every: int = 1,
              n_kills: int = 1, n_straggles: int = 1,
              n_ckpt_crashes: int = 0, verbose: bool = True) -> dict:
    """One elastic training run under injected faults (the tentpole loop).

    The local devices are partitioned into ``n_hosts`` simulated hosts
    (host h owns a contiguous block of whole data-parallel rows).  Each
    step: pull the cursor's batch, train, then ``injector.tick`` — beats,
    straggle decay, and fault events on the virtual clock.  When the
    monitor times a host out (or the mitigator demands an eviction), the
    restart state machine runs:

        BACKOFF  RestartPolicy.next_delay (virtual seconds, budget-limited)
        RESCALE  plan_rescale drops the lost hosts' dp rows, model axis
                 intact; ft.rescale_rules re-derives the sharding rules on
                 the survivor mesh
        RESTORE  restore_checkpoint onto the new mesh's shardings (newest
                 checkpoint passing the torn-write gate; fresh determinstic
                 init if none exists yet)
        REPLAY   the data pipeline is rebuilt at the restored cursor — the
                 stream is a pure function of (seed, step), so every batch
                 after restart is byte-identical to the uninterrupted run

    Returns per-step losses/batch fingerprints plus a restart log; loss-
    curve continuity against a fault-free run is asserted by
    ``repro.testing.check_chaos`` (fp tolerance across the mesh change).
    """
    devices = jax.devices()
    n_dev = len(devices)
    if n_dev % n_hosts:
        raise ValueError(f"{n_dev} devices not divisible into "
                         f"{n_hosts} hosts")
    devices_per_host = n_dev // n_hosts
    if n_dev % model_axis or devices_per_host % model_axis:
        raise ValueError(
            f"model axis {model_axis} must divide both the device count "
            f"{n_dev} and devices/host {devices_per_host} (hosts own whole "
            f"dp rows — AraXL loses clusters, never lanes)")
    dp = n_dev // model_axis

    cfg = get_smoke_config(arch)
    opt_cfg = OptConfig(lr=lr, warmup_steps=max(2, steps // 10),
                        total_steps=steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)
    if ckpt_dir is None:
        ckpt_dir = tempfile.mkdtemp(prefix="repro_chaos_ckpt_")
    mgr = CheckpointManager(ckpt_dir, keep=3)

    schedule = (ChaosSchedule.parse(chaos_spec) if chaos_spec is not None
                else ChaosSchedule.from_seed(
                    chaos_seed, steps=steps, n_hosts=n_hosts,
                    n_kills=n_kills, n_straggles=n_straggles,
                    n_ckpt_crashes=n_ckpt_crashes))
    injector = FaultInjector(schedule, n_hosts=n_hosts, timeout_s=timeout_s,
                             base_step_s=base_step_s)
    policy = RestartPolicy(max_restarts=max_restarts, backoff_s=backoff_s,
                           clock=injector.clock)

    mesh = _host_mesh(devices, dp, model_axis)
    rules = default_rules(mesh, batch=global_batch)
    state = _place_state(cfg, opt_cfg, seed, rules)
    step_fn = jax.jit(make_train_step(cfg, rules, opt_cfg,
                                      n_microbatches=n_microbatches))
    pipe = Pipeline(dcfg, start_step=0)

    losses_by_step: dict[int, float] = {}
    fingerprints: dict[int, int] = {}
    restarts: list[dict] = []
    timeline: list[dict] = []
    tear_next_save = False
    steps_executed = 0
    step = 0
    while step < steps:
        assert pipe.cursor == step, (pipe.cursor, step)
        batch_np = next(pipe)
        fp = _fingerprint(batch_np)
        prev = fingerprints.get(step)
        assert prev is None or prev == fp, \
            f"replay diverged at step {step}: {prev} != {fp}"
        fingerprints[step] = fp
        state, metrics = step_fn(state, {"tokens": jnp.asarray(batch_np)})
        loss = float(metrics["loss"])
        losses_by_step[step] = loss
        steps_executed += 1

        status = injector.tick(step)
        tear_next_save = tear_next_save or status.tear_next_save
        if verbose and (step % log_every == 0 or step == steps - 1):
            print(f"[chaos] step {step:4d} loss {loss:8.4f} "
                  f"mesh {dict(mesh.shape)} t={injector.clock():.1f}s "
                  f"alive={sorted(injector.alive)}", flush=True)

        if (step + 1) % ckpt_every == 0:
            mgr.save_async(state, step + 1,
                           extra={"mesh_shape": list(mesh.devices.shape),
                                  "global_batch": global_batch,
                                  "data_cursor": pipe.cursor})
            if tear_next_save:
                mgr.wait()                     # durable, then corrupted
                tear_checkpoint(ckpt_dir, step + 1)
                timeline.append({"step": step, "event": "ckpt_torn",
                                 "ckpt_step": step + 1})
                tear_next_save = False

        lost = status.lost
        if lost:
            mgr.wait()                         # flush + surface async errors
            if not policy.should_restart():
                raise RuntimeError(
                    f"restart budget exhausted after {policy.restarts} "
                    f"restarts (lost hosts {lost})")
            delay = policy.next_delay()
            injector.clock.advance(delay)      # virtual backoff, no sleep
            injector.evict(lost)
            restore_step = latest_step(ckpt_dir) or 0
            plan = plan_rescale(
                old_devices=mesh.devices.size, lost_hosts=len(lost),
                devices_per_host=devices_per_host,
                mesh_axes=tuple(mesh.devices.shape),
                global_batch=global_batch, restore_step=restore_step)
            if plan.new_global_batch != global_batch:
                raise ValueError(
                    f"global batch {global_batch} not divisible by the "
                    f"rescaled dp={plan.new_mesh_shape[0]} — bit-identical "
                    f"replay needs a batch divisible by every survivable "
                    f"dp size ({plan.notes})")
            mesh, rules = rescale_rules(plan, injector.failed,
                                        devices_per_host, devices=devices)
            if latest_step(ckpt_dir) is not None:
                state, rstep, _ = restore_checkpoint(
                    ckpt_dir, abstract_train_state(cfg, opt_cfg),
                    shardings=train_state_shardings(cfg, opt_cfg, rules))
                rstep = int(rstep)
            else:                              # killed before the first save
                state, rstep = _place_state(cfg, opt_cfg, seed, rules), 0
            step_fn = jax.jit(make_train_step(cfg, rules, opt_cfg,
                                              n_microbatches=n_microbatches))
            pipe.close()
            pipe = Pipeline(dcfg, start_step=rstep)
            restarts.append({
                "detected_at_step": step, "lost_hosts": list(lost),
                "restore_step": rstep, "backoff_s": delay,
                "new_mesh_shape": list(plan.new_mesh_shape),
                "new_devices": plan.new_devices, "notes": plan.notes})
            timeline.append({"step": step, "event": "restart",
                             "lost": list(lost), "restore_step": rstep})
            if verbose:
                print(f"[chaos] RESTART #{len(restarts)}: lost {list(lost)} "
                      f"at step {step}, backoff {delay:.1f}s, restored "
                      f"step {rstep} onto {plan.new_mesh_shape} "
                      f"({plan.notes})", flush=True)
            step = rstep
            continue
        step += 1

    mgr.wait()
    pipe.close()
    losses = [losses_by_step[s] for s in range(steps)]
    return {"losses": losses, "losses_by_step": losses_by_step,
            "final_loss": losses[-1] if losses else None,
            "fingerprints": fingerprints, "restarts": restarts,
            "n_restarts": len(restarts), "timeline": timeline,
            "chaos_spec": schedule.to_spec(), "ckpt_dir": ckpt_dir,
            "steps_executed": steps_executed,
            "final_mesh_shape": list(mesh.devices.shape),
            "virtual_seconds": injector.clock()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full", action="store_true",
                    help="full published config (pod scale)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--chaos", action="store_true",
                    help="elastic-training chaos harness: injected host "
                         "kills/straggles, checkpoint-rescale restarts, "
                         "bit-identical data replay")
    ap.add_argument("--procs", action="store_true",
                    help="with --chaos: run each simulated host as a real "
                         "OS worker process with socket heartbeats; kill@S "
                         "delivers an actual SIGKILL and detection runs on "
                         "real-clock deadlines (repro.ft.cluster)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-spec", default=None,
                    metavar="kill@S:hH,straggle@S:hH:xF:dD,ckpt_crash@S",
                    help="explicit fault schedule (overrides --chaos-seed)")
    ap.add_argument("--hosts", type=int, default=2,
                    help="simulated hosts the local devices split into")
    ap.add_argument("--model-axis", type=int, default=2)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=3.5,
                    help="heartbeat timeout (virtual seconds)")
    ap.add_argument("--max-restarts", type=int, default=3)
    args = ap.parse_args()
    if args.procs and not args.chaos:
        ap.error("--procs requires --chaos")
    compile_cache.enable()
    if args.chaos and args.procs:
        from repro.ft.cluster import ClusterSupervisor
        spec = args.chaos_spec
        if spec is None:
            # seeded schedule, procs-compatible events only (straggles are
            # virtual-clock-only: real slowness cannot be injected
            # deterministically into an OS process)
            spec = ChaosSchedule.from_seed(
                args.chaos_seed, steps=args.steps, n_hosts=args.hosts,
                n_kills=1, n_straggles=0, n_ckpt_crashes=0).to_spec()
        # the parent stays off JAX: each worker owns its (emulated) devices
        sup = ClusterSupervisor(
            args.arch, steps=args.steps, n_hosts=args.hosts,
            model_axis=args.model_axis,
            global_batch=args.batch, seq_len=args.seq, lr=args.lr,
            ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
            chaos_spec=spec, timeout_s=args.timeout,
            max_restarts=args.max_restarts,
            n_microbatches=args.microbatches)
        out = sup.run()
        print(f"[chaos] done (procs): {out['n_restarts']} restart(s) "
              f"across {out['epochs']} epoch(s), final mesh "
              f"{out['final_mesh_shape']}, first loss "
              f"{out['losses'][0]:.4f} final {out['final_loss']:.4f} "
              f"(schedule: {out['chaos_spec'] or 'none'})")
        return
    if args.chaos:
        out = run_chaos(args.arch, steps=args.steps,
                        chaos_seed=args.chaos_seed,
                        chaos_spec=args.chaos_spec, n_hosts=args.hosts,
                        model_axis=args.model_axis, global_batch=args.batch,
                        seq_len=args.seq, lr=args.lr, ckpt_dir=args.ckpt,
                        ckpt_every=args.ckpt_every, timeout_s=args.timeout,
                        max_restarts=args.max_restarts,
                        n_microbatches=args.microbatches)
        print(f"[chaos] done: {out['n_restarts']} restart(s), "
              f"final mesh {out['final_mesh_shape']}, "
              f"first loss {out['losses'][0]:.4f} "
              f"final {out['final_loss']:.4f} "
              f"(schedule: {out['chaos_spec'] or 'none'})")
        return
    out = run(args.arch, smoke=not args.full, steps=args.steps,
              global_batch=args.batch, seq_len=args.seq, lr=args.lr,
              ckpt_dir=args.ckpt, n_microbatches=args.microbatches)
    print(f"[train] done: first loss {out['losses'][0]:.4f} "
          f"final {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
