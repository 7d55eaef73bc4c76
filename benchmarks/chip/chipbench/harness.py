"""What every driver shares: spans, the compile counter, configurations
from JSON, the checks and the result line."""
from __future__ import annotations

import collections.abc
import contextlib
import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import typing
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
# lowering and compiling; a jit call that only looks up a cached trace
# reports a trace event but neither of these
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts the programs JAX lowers or compiles while ``on`` is set."""

    def __init__(self):
        import jax.monitoring as mon

        self.on = False
        self.count = 0

        def listen(event, *args, **kwargs):
            if self.on and event in COMPILE_EVENTS:
                self.count += 1

        mon.register_event_duration_secs_listener(listen)


@contextlib.contextmanager
def span(name: str, traced: bool):
    """One of the harness's own host spans: a ``TraceAnnotation`` in the
    profiler's trace when the run is traced, nothing otherwise."""
    if traced:
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield
    else:
        yield


class Profiler:
    """The profiler over some of the window's calls: ``start`` traces into
    a fresh directory under ``TMPDIR``, ``stop`` (idempotent) ends it; the
    caller reads ``path`` and removes it.  A trace holds only so many
    device events, so a long window is traced in part.  The device traces
    XLA programs alone (``TRACE_ONLY_XLA``): the readers need no other
    device events, and with them the TPU's trace buffers overflowed on
    short back-to-back programs and dropped some of the ops."""

    def __init__(self, enabled: bool):
        self.enabled, self.on, self.path = enabled, False, None

    def start(self) -> None:
        if self.enabled:
            import jax

            self.path = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
            jax.profiler.start_trace(self.path, profiler_options=opts)
            self.on = True

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()
            self.on = False

    def remove(self) -> None:
        if self.path:
            shutil.rmtree(self.path, ignore_errors=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def from_json(tp, value):
    """A JSON value as the type ``tp`` names: a dataclass from an object,
    field by field and nested ones alike; a tuple from a list; the int
    keys of a mapping from the strings JSON gives them."""
    if typing.get_origin(tp) is typing.Union:
        if value is None:
            return None
        tp = next(a for a in typing.get_args(tp) if a is not type(None))
    if dataclasses.is_dataclass(tp) and isinstance(value, dict):
        hints = typing.get_type_hints(tp)
        return tp(**{k: from_json(hints[k], v) for k, v in value.items()})
    origin, args = typing.get_origin(tp), typing.get_args(tp)
    if origin is tuple and isinstance(value, list):
        return tuple(value)
    if (origin in (dict, collections.abc.Mapping) and isinstance(value, dict)
            and args and args[0] is int):
        return {int(k): v for k, v in value.items()}
    return value


def run_checks(names: List[str], run: Dict, checks: "Checks") -> None:
    """Hold a run to its references: each of ``checks/<name>.py``."""
    for name in names:
        load_module(HERE / "checks" / f"{name}.py",
                    "chipbench_check_" + name).check(run, checks)


def read_per_layer(names: List[str], ctx: Dict) -> Dict[str, dict]:
    """Run each metric's reader (``metrics/<name>.py``); a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for name in names:
        mod = load_module(HERE / "metrics" / f"{name}.py",
                          "chipbench_metric_" + name.replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


def device_info(devices, n: int) -> dict:
    peak = 0
    for d in devices[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n, "memory_peak_bytes": peak}


class Checks:
    """Numbers compared with their limits; ``correct`` iff all hold."""

    def __init__(self, limits: Dict[str, float]):
        self.limits = limits
        self.items: List[tuple] = []

    def add(self, name: str, value: float) -> None:
        if name not in self.limits:
            raise KeyError(f"no limit for {name!r}")
        self.items.append((name, float(value), float(self.limits[name])))

    @property
    def correct(self) -> bool:
        # a NaN reading fails: it is not below its limit
        return bool(self.items) and all(v <= lim for _, v, lim in self.items)

    def failed(self) -> List[str]:
        return [n for n, v, lim in self.items if not v <= lim]

    def as_dict(self) -> Dict[str, dict]:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def report(self, stream=sys.stderr) -> None:
        for n, v, lim in self.items:
            ok = "ok" if v <= lim else "FAIL"
            print(f"check {n}: {v!r} limit {lim!r} {ok}", file=stream)


def result_line(checks: Checks, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": checks.correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.as_dict()
    return json.dumps(out)


def derive_seed(seed: int) -> int:
    """The run's ``--seed`` (any whole number) as a 31-bit program seed."""
    import numpy as np

    return int(np.random.SeedSequence(int(seed) % (1 << 64))
               .generate_state(1)[0] & 0x7FFFFFFF)


def limits_for(cell: str) -> Dict[str, float]:
    return json.loads((HERE / "limits" / f"{cell}.json").read_text())


def now() -> float:
    return time.perf_counter()
