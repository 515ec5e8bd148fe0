"""Chip smoke run: phi3-mini-3.8b at its published widths and full depth,
served on a TPU through ``repro.launch.serve``, its answers checked there.

    python chip_smoke.py               # one chip: dense, then paged+chunked
    python chip_smoke.py --four-chips  # the 2x2 mesh against one chip

Weights are random (seed 0).  Each phase prints its compile seconds,
requests and tokens, wall seconds and the device's peak HBM so far.  The
answers are checked with one teacher-forced forward per phase (the
``forward_train`` trunk): every generated token must lie within
:data:`LOGIT_MARGIN` of its row's maximum logit, and the two paths must
agree on every request's first token up to a bf16 tie (:func:`agreement`).
The last line of stdout
is ``{"ok": true, "device": {...}}``; any failed check exits nonzero
before it.  Without a TPU the script exits nonzero at once.

The script is one process: it never starts a child that needs the chip.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "phi3-mini-3.8b"
SEED = 0
N_REQUESTS = 6
MAX_NEW = 32
MAX_SEQ = 1024
MAX_BATCH = 4
CHUNK = 256
#: one teacher-forced shape for every request: prompt (at most 20 tokens,
#: ``launch.serve.PROMPT_LENS``) plus MAX_NEW generated, padded at the end
#: (causal attention: padding never reaches the rows that are checked)
FORCED_LEN = 64
#: how far below its row's maximum a served token's teacher-forced logit
#: may lie.  The served token is the argmax of a logit row computed by
#: another program (one-token decode steps over a bf16 KV cache, padded
#: chunks) than the forward that checks it.  Both round the residual
#: stream to bf16 (8 significant bits) after every sublayer, in different
#: orders, over 64 sublayers; the logits are unit-scale draws whose top
#: values sit near 4, where one bf16 step is 2^-5 = 0.03125.  Eight such
#: steps bound that drift; a wrong token (a different row's argmax) sits
#: a typical top-two gap (~0.2) or more below.
LOGIT_MARGIN = 0.25

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Seconds spent in XLA compilation (or reading it back from the
    persistent cache) since the clock was started."""

    def __init__(self):
        import jax
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.secs += secs


def peak_hbm(devices) -> int:
    """The largest device-memory high-water mark among ``devices``."""
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def serve_phase(name, device, clock, **kw):
    """One ``launch.serve.run`` at full width; returns {rid: (prompt,
    tokens)} after checking that every request completed."""
    from repro.launch import serve
    from repro.testing.timing import now

    c0, t0 = clock.secs, now()
    finished = serve.run(ARCH, smoke=False, n_requests=N_REQUESTS,
                         max_new=MAX_NEW, max_batch=MAX_BATCH,
                         max_seq=MAX_SEQ, seed=SEED, **kw)
    wall = now() - t0
    toks = sum(len(r.out) for r in finished)
    peak = peak_hbm([device])
    print(f"[{name}] compile_s={clock.secs - c0:.3f} "
          f"requests={len(finished)}/{N_REQUESTS} tokens={toks} "
          f"wall_s={wall:.3f} peak_hbm_bytes={peak}", flush=True)
    if len(finished) != N_REQUESTS:
        _fail(f"{name}: {len(finished)} of {N_REQUESTS} requests finished")
    return {r.rid: (list(map(int, r.prompt)), list(r.out))
            for r in finished}


def forced_gaps(params, cfg, rules, served):
    """Teacher-forced check on the device: for each request, one forward
    over prompt + generated tokens.  Returns {rid: (gaps, maxima)} per
    generated token: the row's maximum logit, and how far below it the
    served token's logit lies."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    rids = sorted(served)
    toks = np.zeros((len(rids), FORCED_LEN), np.int32)
    rows = np.zeros((len(rids), MAX_NEW), np.int32)
    n_out = []
    for i, rid in enumerate(rids):
        prompt, out = served[rid]
        seq = prompt + out
        if len(seq) > FORCED_LEN:
            _fail(f"request {rid}: {len(seq)} tokens exceed FORCED_LEN")
        toks[i, :len(seq)] = seq
        # generated token j is the argmax of the row at position plen-1+j
        rows[i, :len(out)] = len(prompt) - 1 + np.arange(len(out))
        n_out.append(len(out))

    @jax.jit
    def gaps(params, toks, rows):
        x = lm.embed_tokens(params, toks, cfg, rules)
        x = lm.trunk(params, x, cfg, rules, jnp.arange(toks.shape[1]))
        logits = lm.logits_fn(params, x, cfg, rules).astype(jnp.float32)
        picked_rows = jnp.take_along_axis(logits, rows[..., None], axis=1)
        nxt = jnp.take_along_axis(toks, rows + 1, axis=1)
        chosen = jnp.take_along_axis(picked_rows, nxt[..., None],
                                     axis=2)[..., 0]
        top = picked_rows.max(axis=-1)
        return top - chosen, top

    g, top = map(np.asarray, gaps(params, jnp.asarray(toks),
                                  jnp.asarray(rows)))
    return {rid: (g[i, :n_out[i]].tolist(), top[i, :n_out[i]].tolist())
            for i, rid in enumerate(rids)}


def check_answers(name, forced):
    worst = max(max(g) for g, _ in forced.values())
    n = sum(len(g) for g, _ in forced.values())
    print(f"[{name}] teacher_forced tokens={n} worst_gap={worst:.4f} "
          f"margin={LOGIT_MARGIN}", flush=True)
    if worst > LOGIT_MARGIN:
        bad = {rid: max(g) for rid, (g, _) in forced.items()
               if max(g) > LOGIT_MARGIN}
        _fail(f"{name}: served tokens below the row maximum by more than "
              f"{LOGIT_MARGIN}: {bad}")


def bf16_step(v: float) -> float:
    """Spacing of bfloat16 values (8 significant bits) at ``v``."""
    return 2.0 ** (math.floor(math.log2(abs(v))) - 7)


def agreement(name, a, b, forced_a, forced_b):
    """Token agreement of two serving paths over the same requests.

    The first token of every request must match — unless the two tokens
    tie: the first row is the same teacher-forced row for both paths (same
    prompt), and both tokens lie within one bf16 step of its maximum.
    Random weights make such ties common (32064 unit-scale logits, one bf16
    step is 2^-5 near the top), and two correct programs that round in
    different orders break them differently."""
    same = total = ties = 0
    for rid in a:
        x, y = a[rid][1], b[rid][1]
        if x[:1] != y[:1]:
            step = bf16_step(forced_a[rid][1][0])
            if max(forced_a[rid][0][0], forced_b[rid][0][0]) > step:
                _fail(f"{name}: request {rid} first token {x[:1]} vs "
                      f"{y[:1]}, not a bf16 tie (gaps "
                      f"{forced_a[rid][0][0]}, {forced_b[rid][0][0]})")
            ties += 1
        total += max(len(x), len(y))
        same += sum(p == q for p, q in zip(x, y))
    print(f"[{name}] first_tokens_agree={len(a) - ties}/{len(a)} "
          f"bf16_ties={ties} token_agreement={same}/{total}="
          f"{same / total:.4f}", flush=True)


def report_blocks():
    """Print the kernel blocks chosen per signature; none may come from
    the interpret-mode autotune table."""
    from repro.kernels import ops
    for (kernel, shape, dtype), (blocks, source) in sorted(
            ops.resolved.items()):
        print(f"[blocks] {kernel} {'x'.join(map(str, shape))} {dtype} "
              f"{blocks} from {source}", flush=True)
    tuned = [k for k, (_, src) in ops.resolved.items() if src == "tuned"]
    if tuned:
        _fail(f"blocks taken from the interpret-mode autotune table: "
              f"{tuned}")
    if not ops.resolved:
        _fail("no Pallas kernel was traced on the TPU path")


def one_chip(device, clock):
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params

    dense = serve_phase("dense", device, clock)
    paged = serve_phase("paged_chunked", device, clock, paged=True,
                        chunk=CHUNK)
    report_blocks()
    cfg = get_config(ARCH)
    rules = default_rules(None)
    params = init_params(lm.model_defs(cfg), jax.random.key(SEED))
    forced = {}
    for name, served in (("dense", dense), ("paged_chunked", paged)):
        forced[name] = forced_gaps(params, cfg, rules, served)
        check_answers(name, forced[name])
    agreement("dense_vs_paged", dense, paged, forced["dense"],
              forced["paged_chunked"])


def four_chips(device, clock):
    """The sharded path users run: ServingEngine on a 2x2 (data x model)
    mesh, then the same requests on one of its chips."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh, parse_launch_topology
    from repro.launch.serve import PROMPT_LENS
    from repro.models import lm
    from repro.parallel.sharding import (default_rules, init_params,
                                         param_shardings)
    from repro.serve import Request, ServeConfig, ServingEngine
    from repro.testing.timing import now

    if len(jax.devices()) < 4:
        _fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    topo = parse_launch_topology("2x2")
    mesh = make_production_mesh(topology=topo)
    cfg = get_config(ARCH)
    defs = lm.model_defs(cfg)
    rules = default_rules(mesh, kv_heads=cfg.n_kv_heads, batch=1)
    scfg = ServeConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size, PROMPT_LENS[i % 2])
               .astype(np.int32) for i in range(N_REQUESTS)]

    def drive(name, engine):
        c0, t0 = clock.secs, now()
        for rid, p in enumerate(prompts):
            engine.submit(Request(rid=rid, prompt=p, max_new_tokens=MAX_NEW))
        finished = engine.run()
        toks = sum(len(r.out) for r in finished)
        peak = peak_hbm(jax.devices()[:4])
        print(f"[{name}] compile_s={clock.secs - c0:.3f} "
              f"requests={len(finished)}/{N_REQUESTS} tokens={toks} "
              f"wall_s={now() - t0:.3f} peak_hbm_bytes_max_chip={peak}",
              flush=True)
        if len(finished) != N_REQUESTS:
            _fail(f"{name}: {len(finished)} of {N_REQUESTS} finished")
        return {r.rid: (list(map(int, r.prompt)), list(r.out))
                for r in finished}

    # each device draws its own shards: no chip holds the whole model
    params = jax.jit(lambda k: init_params(defs, k),
                     out_shardings=param_shardings(defs, rules))(
                         jax.random.key(SEED))
    mesh_eng = ServingEngine(cfg, params, rules, scfg, topology=topo)
    sharded = drive("mesh_2x2", mesh_eng)
    del mesh_eng
    params = jax.device_put(params, SingleDeviceSharding(device))
    one_rules = default_rules(None)
    with jax.default_device(device):
        single = drive("one_chip", ServingEngine(cfg, params, one_rules,
                                                 scfg))
        forced = {}
        for name, served in (("mesh_2x2", sharded), ("one_chip", single)):
            forced[name] = forced_gaps(params, cfg, one_rules, served)
            check_answers(name, forced[name])
        agreement("mesh_vs_one_chip", sharded, single, forced["mesh_2x2"],
                  forced["one_chip"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 2x2 mesh and compare with one chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{device.platform!r} ({device.device_kind})", file=sys.stderr)
        return 2

    from repro.launch import compile_cache
    print(f"[setup] compile_cache={compile_cache.enable()} "
          f"device={device.device_kind} count={len(devices)}", flush=True)
    clock = CompileClock()
    if args.four_chips:
        four_chips(device, clock)
    else:
        one_chip(device, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
