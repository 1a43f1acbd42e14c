"""What every cell shares: finding a cell's files by name, the device check,
the seed, the profiler window, per-layer metric readers and the result line.

Everything here is found from `BENCHMARK.json` by name: a cell names its
configuration (`bench/configs/<config>.json`) and its traffic mix
(`bench/traffic/<traffic>.json`); the mix's `kind` names the driver
(`bench/drivers/<kind>.py`); each per-layer metric is read by
`bench/metrics/<metric name>.py`; the limits of the output comparison of a
cell are in `bench/limits/<cell>.json`. A new cell, mix or metric is new
files plus new entries, with no edit to a file that is already here.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A cell that cannot run as declared (missing file, unknown device)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path = ROOT


@dataclass
class Check:
    """One number compared with the plain reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class RunResult:
    """What a driver hands back to `finish`."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    memory_peak_bytes: int
    context: Dict[str, Any] = field(default_factory=dict)
    trace_dir: Optional[Path] = None
    notes: List[str] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def load_json(path: Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {path.relative_to(ROOT)}") from None


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", cells)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    limits_path = root / "bench" / "limits" / f"{name}.json"
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / cfg_entry["file"]),
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json"),
                limits=load_json(limits_path)["limits"],
                end_to_end=e2e, per_layer=per_layer, root=root)


# ---------------------------------------------------------------------------
# configuration files -> the program's config objects
# ---------------------------------------------------------------------------

def model_config(conf: Dict):
    """The program's ModelConfig for a configuration file: the published
    keys under `config`, the program's own settings under `arch`."""
    from repro.configs.base import ModelConfig
    c, a = conf["config"], conf["arch"]
    if c.get("tie_word_embeddings"):
        raise BenchError(f"{conf['name']}: the program has no tied LM head")
    if c["hidden_act"] != "silu":
        raise BenchError(f"{conf['name']}: hidden_act {c['hidden_act']!r}")
    heads = c["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family=a["family"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        vocab=c["vocab_size"], n_heads=heads,
        n_kv=c["num_key_value_heads"],
        head_dim=c.get("head_dim", c["hidden_size"] // heads),
        d_ff=c["intermediate_size"], qk_norm=bool(a["qk_norm"]),
        qkv_bias=bool(c.get("attention_bias", False)),
        rope_theta=float(c["rope_theta"]), gated_mlp=bool(a["gated_mlp"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        param_dtype=c["torch_dtype"])


def peft_config(conf: Dict):
    from repro.configs.base import PEFTConfig
    p = dict(conf["peft"])
    p["target_modules"] = tuple(p["target_modules"])
    return PEFTConfig(**p)


# ---------------------------------------------------------------------------
# devices, seeds, peaks
# ---------------------------------------------------------------------------

def require_tpu(chips: int) -> List:
    """The first `chips` TPU devices; BenchError when JAX finds no TPU or
    too few. A benchmark number never comes from another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform}; the "
                         "benchmark never falls back to another platform")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return list(devs[:chips])


def device_info(devices: Sequence) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_memory_bytes(devices: Sequence) -> int:
    """Peak bytes in use on the fullest of `devices` (0 where the backend
    keeps no statistics)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def peaks_for(device_kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def prng_key(seed: int, salt: str = ""):
    """A JAX key from a whole-number seed of any size (past 2**32 too)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(salt.encode()) & 0x7FFFFFFF)


def np_rng(seed: int, *salt: int):
    import numpy as np
    return np.random.default_rng([seed, *salt])


# ---------------------------------------------------------------------------
# profiler window
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def profiled(cell: Cell, on: bool):
    """Trace the block with JAX's profiler when `on`; yields the directory
    (or None). The block is annotated `bench.window`."""
    import jax
    if not on:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield None
        return
    base = cell.root / ".bench_trace"
    base.mkdir(parents=True, exist_ok=True)
    d = Path(tempfile.mkdtemp(prefix=f"{cell.name}.", dir=base))
    # host spans from TraceMe annotations (jit dispatch, transfers, the
    # benchmark's own `bench.*` spans), but no per-call Python tracing: it
    # would slow the host under test and multiply the trace's size
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield d
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------

def load_reader(name: str, root: Path = ROOT) -> Callable[[Dict], Any]:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader bench/metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, ctx: Dict) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def finish(cell: Cell, res: RunResult, devices: Sequence, trace: bool,
           out=None, err=None) -> Dict:
    """Build the result object, print the compared numbers as the last
    lines of standard error and the result as the last line of standard
    output. Returns the result object."""
    out = out or sys.stdout
    err = err or sys.stderr
    device = dict(device_info(devices),
                  memory_peak_bytes=int(res.memory_peak_bytes))
    result: Dict[str, Any] = {"correct": res.correct,
                              "attempted": int(res.attempted),
                              "failed": int(res.failed)}
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.summarize(res.trace_dir, len(devices))
        ctx = dict(res.context, trace=summary,
                   peaks=peaks_for(devices[0].device_kind),
                   chips=len(devices))
        result["metrics"] = read_per_layer(cell, ctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = summary.breakdown()
        shutil.rmtree(res.trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {
            m["name"]: {"value": float(res.end_to_end[m["name"]]),
                        "unit": m["unit"]} for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {c.name: {"value": _finite(c.value),
                                 "limit": c.limit} for c in res.checks}
    for m in result["metrics"].values():
        m["value"] = _finite(m["value"])
    for note in res.notes:
        print(f"[bench] {note}", file=err)
    for c in res.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def _finite(x: float):
    """JSON has no NaN or infinity: a number that is not finite is null."""
    return x if math.isfinite(x) else None


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)
