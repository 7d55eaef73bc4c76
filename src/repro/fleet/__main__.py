"""``python -m repro.fleet``: the fleet smokes on a >=2-CPU-device host.

Forces a 2-device CPU topology (when no accelerator/topology is already
configured) BEFORE jax initializes, so the 2-plane smoke actually
exercises plane sharding over a real multi-device mesh — the CI proof
that join/leave/failure events run on device across the mesh with <= 1
host sync per revolution.

``--scenario degraded`` runs the degraded-ops smoke instead: eclipse
windows + one Byzantine slot + epidemic faults with robust aggregation,
asserting finite losses and bit-exact host-prefix action parity
(:func:`repro.fleet.scenarios._smoke_degraded`).

Env knobs (small-machine CI): ``REPRO_FLEET_SMOKE_SATS`` (default 8),
``REPRO_FLEET_SMOKE_PLANES`` (default 2), ``REPRO_FLEET_SMOKE_REVS``
(default 2).
"""
import os
import sys

if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

args = sys.argv[1:]
scenario = "baseline"
if args:
    if args[0] != "--scenario" or len(args) != 2 \
            or args[1] not in ("baseline", "degraded"):
        raise SystemExit("usage: python -m repro.fleet "
                         "[--scenario baseline|degraded]")
    scenario = args[1]

from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

kw = dict(
    n_sats=int(os.environ.get("REPRO_FLEET_SMOKE_SATS", "8")),
    n_planes=int(os.environ.get("REPRO_FLEET_SMOKE_PLANES", "2")),
    n_revolutions=int(os.environ.get("REPRO_FLEET_SMOKE_REVS", "2")))

if scenario == "degraded":
    from repro.fleet.scenarios import _smoke_degraded  # noqa: E402

    _smoke_degraded(**kw)
else:
    from repro.fleet.engine import _smoke  # noqa: E402

    _smoke(**kw)
