"""Least bytes one batch of the keyed count-window step must move, from
its shapes alone, and the names of its modules in a device trace."""

#: XLA module names of the step in a device trace: ``jit_step`` is one
#: batch through the fused Map+Filter prelude and the window step,
#: ``jit_mega`` the K-batch scan of the same
MODULES = r"^jit_(step|mega)$"

KEY, VALUE, TS, WID = 4, 4, 8, 8     # bytes of a lane's element


def parts(cfg: dict):
    """``(lanes in, state, fired rows out)`` in bytes for one batch."""
    g = cfg["graph"]
    lanes_in = g["batch"] * (KEY + VALUE + TS)
    # pane-ring state of the keys: win/slide partial sums and a count
    state = g["n_keys"] * (g["win"] // g["slide"] * VALUE + 8)
    # 7 of 8 keys pass the filter; one row fires per slide kept tuples
    fired = g["batch"] * 7 / 8 / g["slide"] * (KEY + WID + VALUE + TS)
    return lanes_in, state, fired


def least_bytes(cfg: dict) -> float:
    lanes_in, state, fired = parts(cfg)
    return lanes_in + 2 * state + fired          # state read and written
