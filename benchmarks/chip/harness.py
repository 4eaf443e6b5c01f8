"""The chip benchmark: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell's entry in
``BENCHMARK.json`` names a configuration (its ``file`` under
``configs/``, with the plain reference the file names beside it) and a
traffic mix (``mixes/<traffic>.json``, whose ``driver`` key picks the
general training or serving driver); each per-layer metric is read by
``metrics/<name>.py``.  A new cell, mix, configuration or metric is new
files and new entries, never an edit.

The run loads, warms up the cell's own shapes, measures for ``--seconds``
and then checks what the timed path produced against the plain reference
(after reading the memory peak and freeing the system's state).  Its last
line on standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``, each compared number with its limit.  It exits
non-zero, and prints no result, where JAX finds no TPU, fewer chips than
the cell asks for, or a chip that is not in ``peaks.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchError(Exception):
    """A run that cannot give a result."""


@dataclasses.dataclass
class Cell:
    """Everything the files say about one cell."""
    root: str                 # checkout root (BENCHMARK.json, src/)
    bench_dir: str            # the benchmark's directory
    manifest: dict
    workload: dict
    config: dict              # the configuration file as run
    mix: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    def reference(self):
        return importlib.import_module(
            f"{__package__}.configs.{self.config['reference']}")

    def per_layer(self) -> list[dict]:
        """The per-layer metrics this cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


def load_cell(name: str, root: str, bench_dir: str = HERE) -> Cell:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    wl = {w["name"]: w for w in manifest["workloads"]}
    if name not in wl:
        raise BenchError(f"no workload {name!r} in {path}")
    workload = wl[name]
    cfgs = {c["name"]: c for c in manifest["configs"]}
    with open(os.path.join(root, cfgs[workload["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "mixes",
                           workload["traffic"] + ".json")) as f:
        mix = json.load(f)
    return Cell(root, bench_dir, manifest, workload, config, mix)


# -- the system under test ------------------------------------------------------


def prepare_program(root: str) -> None:
    """Put the system on the path and its caches at fixed places in the
    checkout, so that a second run of a cell finds every plan and every
    compiled program of the first."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the system under test is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    cache = os.path.join(root, ".cache", "bench")
    os.environ["REPRO_CSSE_CACHE"] = os.path.join(cache, "csse")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(cache, "autotune")
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


LM_KEYS = {  # configuration file key -> LMConfig field
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "qkv_bias": "qkv_bias", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
}
TNN_KEYS = ("method", "rank", "num_factors", "targets", "backend")


def build_model(cell: Cell, mesh=None):
    """The system's model for the cell's configuration, built as its
    launchers build it (``launch/steps.build_model`` from the registry's
    architecture and its default TNN block), with the file's values set.
    Returns ``(model, lm_config)``; fails where the system's parameter
    layout differs from the reference's."""
    import jax
    from repro.configs import base as cfgbase
    from repro.launch import steps
    from repro.models.lm import LM

    c = cell.config
    arch = cfgbase.get(c["registry"])
    tnn = dataclasses.replace(
        arch.tnn_default,
        **{k: (tuple(v) if k == "targets" else v)
           for k, v in c["tnn"].items() if k in TNN_KEYS})
    if mesh is not None and cell.mix.get("tnn_mesh"):
        tnn = dataclasses.replace(tnn, mesh=mesh,
                                  mesh_axes=tuple(cell.mix["tnn_mesh"]))
    _, lm = steps.build_model(arch, tnn=tnn)
    lm = dataclasses.replace(lm, **{f: c[k] for k, f in LM_KEYS.items()
                                    if k in c})
    for k, f in LM_KEYS.items():
        if k in c and getattr(lm, f) != c[k]:
            raise BenchError(f"{k}: the system runs {getattr(lm, f)!r}, "
                             f"the file states {c[k]!r}")
    for k in ("compute_dtype", "param_dtype"):
        if jax.numpy.dtype(getattr(lm, k)).name != c[k]:
            raise BenchError(f"{k}: the system runs {getattr(lm, k)}, "
                             f"the file states {c[k]}")
    model = LM(lm)
    ref = cell.reference()
    got = jax.tree.map(lambda x: tuple(x.shape),
                       jax.eval_shape(model.init, jax.random.key(0)))
    want = ref.param_shapes(c)
    if got != want:
        raise BenchError("the system's parameter layout differs from the "
                         "reference's")
    return model, lm


def make_params(cell: Cell, seed: int, shardings=None):
    """The weights, made on the device in one jitted call from the seed."""
    import jax
    ref = cell.reference()
    fn = jax.jit(lambda k: ref.init_params(cell.config, k),
                 out_shardings=shardings)
    return fn(jax.random.key(seed))


def free_device() -> None:
    """Drop what is no longer referenced, so the reference runs in the
    memory the system has given back."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


# -- devices -----------------------------------------------------------------


def devices_for(cell: Cell, require_chip: bool):
    import jax

    from .peaks import peak_for
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
        if len(devs) < cell.chips:
            raise BenchError(f"{cell.chips} chips asked for, "
                             f"{len(devs)} present")
        peak = peak_for(kind)
    else:
        peak = peak_for("TPU v5 lite")
    return devs[:cell.chips], kind, peak


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- per-layer metrics -----------------------------------------------------------


def read_metrics(cell: Cell, run: dict) -> dict:
    out = {}
    for m in cell.per_layer():
        path = os.path.join(cell.bench_dir, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the run ---------------------------------------------------------------------


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             fault: str | None = None) -> dict:
    """One run of a cell; returns the result object."""
    devs, kind, peak = devices_for(cell, require_chip)
    prepare_program(cell.root)
    driver = importlib.import_module(f"{__package__}.{cell.mix['driver']}_cell")
    return driver.run(cell, devs=devs, kind=kind, peak=peak, seed=seed,
                      seconds=seconds, trace=trace, t_start=t_start,
                      fault=fault)


def emit(result: dict) -> None:
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line = {k: v for k, v in result.items() if k != "checks"}
    line["checks"] = checks
    print(json.dumps(line))


def main(argv=None, *, t_start: float | None = None, root: str | None = None,
         bench_dir: str = HERE, require_chip: bool = True,
         fault: str | None = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.getcwd()
    try:
        cell = load_cell(args.workload, root, bench_dir)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start,
                          require_chip=require_chip, fault=fault)
    except (BenchError, OSError, ValueError, KeyError, RuntimeError,
            ImportError) as e:
        traceback.print_exc()
        print(f"benchmark: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    emit(result)
    return 0
