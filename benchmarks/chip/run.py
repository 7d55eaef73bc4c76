"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
file, its traffic mix (``traffic/<name>.json``, which names the driver
under ``drivers/``) and its limits (``limits/<cell>.json``) are data.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics.
Either way the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced) and, last, ``checks``: every number compared
with its limit, which also end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result.  JAX's compilation cache is the program's
(``JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache/``), and
keeps every program.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_cell(name: str):
    """The cell's ``BENCHMARK.json`` entry, configuration and traffic."""
    from chipbench import manifest

    man = manifest.load(ROOT)
    cell = manifest.workload(man, name)
    cfg_file = manifest.config_entry(man, cell["config"])["file"]
    cfg = json.loads((ROOT / cfg_file).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return man, cell, cfg, traffic


def enable_cache() -> None:
    """The program's persistent compilation cache, every program kept in
    it so that only a checkout's first run compiles; call before JAX
    initialises."""
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(man, cell, cfg, traffic, *, seed: int, seconds: float,
             trace: bool, devices, peaks: dict, t_start: float) -> dict:
    """Everything of a run after the look for chips: the driver's set-up,
    window and checks, and the metrics this cell reports."""
    from chipbench import harness, manifest

    kind = "per_layer" if trace else "end_to_end"
    ctx = {"cell": cell["name"], "cfg": cfg, "traffic": traffic,
           "seed": harness.derive_seed(seed), "seconds": seconds,
           "trace": trace, "chips": cell["chips"], "devices": devices,
           "peaks": peaks, "limits": harness.limits_for(cell["name"]),
           "per_layer": [m["name"] for m in manifest.metrics_for(
               man, cell["name"], "per_layer")],
           "t_start": t_start, "counter": harness.CompileCounter()}
    driver = harness.load_module(HERE / "drivers" / f"{traffic['driver']}.py",
                                 "chipbench_driver_" + traffic["driver"])
    res = driver.run(ctx)
    want = {m["name"] for m in manifest.metrics_for(man, cell["name"], kind)}
    missing = want - set(res["metrics"])
    if missing and not trace:
        raise RuntimeError(f"end-to-end metrics missing: {sorted(missing)}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    from chipbench.peaks import peaks_for

    man, cell, cfg, traffic = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    res = run_cell(man, cell, cfg, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices, peaks=peaks_for(devices[0].device_kind),
                   t_start=T_START)
    res["checks"].report()
    print(harness.result_line(res["checks"], res["attempted"],
                              res["failed"], res["metrics"], res["device"],
                              res["breakdown"]))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    sys.exit(main())
