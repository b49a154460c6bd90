"""The key-sharded count-window step on a mesh, per chip: the whole batch
comes in (every chip filters it for its own keys); the chip's share of
the state is read and written and its share of the rows fires.  The
roofline share is taken against one chip's HBM and the mean device time
over the chips, so the bytes are one chip's."""
from benchmark.harness import load_module

_one = load_module("roofline", "ffat_cb_step")
#: on a mesh the prelude is its own module, ``jit_local``
MODULES = r"^jit_(step|mega|local)$"


def least_bytes(cfg: dict) -> float:
    lanes_in, state, fired = _one.parts(cfg)
    n = cfg["graph"]["mesh"]
    return lanes_in + (2 * state + fired) / n
