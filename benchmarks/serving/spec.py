"""Finds each piece of the benchmark by its name, and checks it.

    configs/<config>.json      one model configuration (sizes, engine)
    families/<family>.py       one architecture family: the weights'
                               layout, the plain reference's forward and
                               the work counts of the configurations
                               that name it (``FAMILY_ATTRS``)
    traffic/<cell>.json        one cell: its configuration and traffic mix
    generators/<kind>.py       one arrival law: ``CLOSED``, ``gaps(spec, u)``
    metrics/<metric>.py        one metric, ``compute(record)``
    peaks.json                 chip peaks keyed by ``device_kind``

A later cell, configuration, family, arrival law or metric is a new file;
nothing here names one.  Every loader takes the directory it reads from,
so a test can point it at a copy.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]                       # the checkout
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

CONFIG_KEYS = ("name", "source", "family", "arch", "overrides",
               "hidden_size", "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "num_hidden_layers", "vocab_size",
               "rms_norm_eps", "rope_theta", "reduced", "assumed",
               "deployment", "engine")
ENGINE_KEYS = ("max_batch", "max_seq", "block_tokens", "pool_tokens",
               "chunk")
CELL_KEYS = ("name", "config", "arrivals", "prompt_len", "output_len",
             "requests", "strata", "check", "trace", "why", "who")
LENGTH_KEYS = ("dist", "median", "sigma", "min", "max")
METRIC_ATTRS = ("NAME", "UNIT", "LAYER", "MOVES", "SOURCE", "compute")
FAMILY_ATTRS = ("KEYS", "check", "program_config", "shapes", "to_program",
                "forward_rows", "matmuls", "weight_map", "head_shape",
                "decode_step", "prefill_chunk")
#: the key under which a loaded configuration keeps the directory it was
#: read from, so that its family is read from the same directory
BASE_KEY = "_base"


class SpecError(ValueError):
    """A benchmark file is missing or does not validate."""


def _name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"{kind} name {name!r} is not a valid name")
    return name


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no file {path}")
    with path.open() as f:
        return json.load(f)


def _need(d: dict, keys, where: str) -> None:
    missing = [k for k in keys if k not in d]
    if missing:
        raise SpecError(f"{where}: missing {missing}")


def _module(path: pathlib.Path, attrs, where: str):
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_serving_{path.parent.name}_{path.stem.replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [a for a in attrs if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"{where}: missing {missing}")
    return mod


def load_config(name: str, base: pathlib.Path = HERE) -> dict:
    c = _json(base / "configs" / f"{_name('config', name)}.json")
    _need(c, CONFIG_KEYS, f"config {name}")
    _need(c["engine"], ENGINE_KEYS, f"config {name} engine")
    if c["name"] != name:
        raise SpecError(f"config file {name} names itself {c['name']}")
    e = c["engine"]
    bt = e["block_tokens"]
    if e["max_seq"] % bt or e["chunk"] % bt or e["max_seq"] % e["chunk"] \
            or e["pool_tokens"] % bt:
        raise SpecError(f"config {name}: block_tokens must tile max_seq, "
                        f"chunk and pool_tokens, and chunk divide max_seq")
    for k in c["reduced"]:
        _name("reduced key", k)
    fam = load_family(c["family"], base)
    _need(c, fam.KEYS, f"config {name} (family {c['family']})")
    fam.check(c)
    c[BASE_KEY] = str(base)
    return c


@functools.cache
def _family(path: pathlib.Path):
    return _module(path, FAMILY_ATTRS, f"family {path.stem}")


def load_family(name: str, base: pathlib.Path = HERE):
    """The module of architecture family ``name`` in ``base``; loaded once
    per file, so that every caller shares its compiled programs."""
    return _family(base.resolve() / "families"
                   / f"{_name('family', name)}.py")


def family(c: dict):
    """The family module of configuration ``c``, from the directory it
    was loaded from (a configuration written in code: this one)."""
    return load_family(c["family"], pathlib.Path(c.get(BASE_KEY, HERE)))


def load_cell(name: str, base: pathlib.Path = HERE) -> dict:
    cell = _json(base / "traffic" / f"{_name('cell', name)}.json")
    _need(cell, CELL_KEYS, f"cell {name}")
    if cell["name"] != name:
        raise SpecError(f"traffic file {name} names itself {cell['name']}")
    for key in ("prompt_len", "output_len"):
        _need(cell[key], LENGTH_KEYS, f"cell {name} {key}")
        lo, mid, hi = (cell[key][x] for x in ("min", "median", "max"))
        if not 1 <= lo <= mid <= hi:
            raise SpecError(f"cell {name} {key}: need 1 <= min <= median "
                            f"<= max")
    if cell.get("strata_by", "prompt_len") not in ("prompt_len",
                                                   "output_len"):
        raise SpecError(f"cell {name}: strata_by must be prompt_len or "
                        f"output_len")
    if not isinstance(cell.get("order_seed", 0), int):
        raise SpecError(f"cell {name}: order_seed must be a whole number")
    _need(cell["arrivals"], ("kind",), f"cell {name} arrivals")
    _need(cell["check"], ("sample_tokens", "logit_gap_limit"),
          f"cell {name} check")
    _need(cell["trace"], ("start_s", "seconds"), f"cell {name} trace")
    load_generator(cell["arrivals"]["kind"], base)
    return cell


def load_generator(kind: str, base: pathlib.Path = HERE):
    return _module(base / "generators" / f"{_name('generator', kind)}.py",
                   ("CLOSED", "gaps"), f"generator {kind}")


def load_metric(name: str, base: pathlib.Path = HERE):
    mod = _module(base / "metrics" / f"{_name('metric', name)}.py",
                  METRIC_ATTRS, f"metric {name}")
    if mod.NAME != name:
        raise SpecError(f"metric file {name} names itself {mod.NAME}")
    return mod


def load_peaks(device_kind: str, base: pathlib.Path = HERE) -> dict:
    table = _json(base / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[str]:
    """The metrics a run of ``cell`` reports, in BENCHMARK.json's order:
    its end-to-end metrics untraced, its per-layer metrics traced.  A
    metric without a ``workloads`` list is every cell's; a per-layer one
    without it goes with every cell that reports the metric it moves."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    return [m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])
            and ("workloads" in m or m["moves"] in e2e)]
