"""Device->host array copies per revolution dispatch: the program's
``fleet.d2h_arrays`` counter over its ``fleet.device_calls``, over the
whole run (each call is one streamed revolution)."""
from chipbench import program

UNIT = "arrays"


def read(ctx):
    reg = program.registry()
    arrays, calls = reg.get("fleet.d2h_arrays"), reg.get("fleet.device_calls")
    if not arrays or not calls:
        return None
    return arrays / calls
