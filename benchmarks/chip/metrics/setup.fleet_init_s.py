"""Host time of the fleet engine's construction, planning included: the
program's ``fleet.init`` span."""
from chipbench import program

UNIT = "s"


def read(ctx):
    return program.histogram_sum(program.registry(), "fleet.init_s")
