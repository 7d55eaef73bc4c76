"""Host time of the first launch of each compiled fleet program: tracing,
lowering and compiling or loading it, up to the call's return (the
program's ``first_launch_s``)."""
from chipbench import program

UNIT = "s"


def read(ctx):
    return program.histogram_sum(program.registry(), "fleet.first_launch_s")
