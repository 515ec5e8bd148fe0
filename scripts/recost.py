"""Recompute cost terms (unrolled p1/p2) for existing dry-run JSONs."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS","")
import json, pathlib, sys, time
sys.path.insert(0, "src")
from repro.configs import SHAPES, get_config
from repro.launch import dryrun as dr
from repro.launch.mesh import make_production_mesh, production_topology
from repro.roofline.analysis import (HW, collective_bytes, extrapolate,
                                     memory_model_bytes, parse_collectives,
                                     roofline_terms)

kinds = set(sys.argv[1:]) or {"prefill"}
mesh = make_production_mesh()
topo = production_topology()
outdir = pathlib.Path("results/dryrun")
for f in sorted(outdir.glob("*pod16x16.json")):
    rec = json.loads(f.read_text())
    if "skipped" in rec or rec["kind"] not in kinds:
        continue
    cfg = get_config(rec["arch"]); shape = SHAPES[rec["shape"]]
    nm = rec["n_microbatches"]; n_dev = rec["devices"]
    t0 = time.time()
    costs = {}
    cshape = dr._cost_shape(shape, nm)
    for n in (1, 2):
        lo, co = dr.lower_cell(dr._variant(cfg, n), cshape, mesh, n_micro=1)
        ca = co.cost_analysis()
        colls = parse_collectives(co.as_text())
        costs[n] = {"flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0)),
                    "wire": collective_bytes(colls)}
        del co, lo
    L = cfg.n_periods
    flops = nm * extrapolate(costs[1]["flops"], costs[2]["flops"], L)
    bytes_ = nm * extrapolate(costs[1]["bytes"], costs[2]["bytes"], L)
    wire = nm * extrapolate(costs[1]["wire"]["total"], costs[2]["wire"]["total"], L)
    rec["per_device"] = {"flops": flops, "bytes": bytes_, "wire_bytes": wire}
    rec["roofline"] = roofline_terms(flops, bytes_, wire)
    mm = memory_model_bytes(cfg, shape, n_dev, nm, topology=topo)
    rec["roofline"]["memory_s_hlo_upper"] = rec["roofline"]["memory_s"]
    rec["roofline"]["memory_s"] = mm / HW["hbm_bw"]
    terms = {k: rec["roofline"][k] for k in ("compute_s","memory_s","collective_s")}
    rec["roofline"]["bottleneck"] = max(terms, key=terms.get)
    rec["roofline"]["step_s_lower_bound"] = max(terms.values())
    mf = rec["model_flops_global"]
    rec["model_vs_hlo_flops"] = mf / (flops*n_dev) if flops else 0.0
    rec["roofline"]["mfu_upper_bound"] = (mf/n_dev/HW["peak_flops"]
        / rec["roofline"]["step_s_lower_bound"]) if rec["roofline"]["step_s_lower_bound"] else 0.0
    rec["recost_unrolled"] = True
    f.write_text(json.dumps(rec, indent=2))
    r = rec["roofline"]
    print(f"[recost] {f.stem}: c={r['compute_s']:.3f} m={r['memory_s']:.3f} "
          f"w={r['collective_s']:.3f} bound={r['bottleneck']} ({time.time()-t0:.0f}s)", flush=True)
