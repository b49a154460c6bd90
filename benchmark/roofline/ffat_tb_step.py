"""Least bytes one batch of the YSB step must move: the filter reads the
event-type lane, the join the ad lane, the window the timestamps; the
state is one count per campaign and open window; rows fire only when a
window closes (none, in most batches)."""

MODULES = r"^jit_(step|mega)$"

KEY, VALUE, TS, COUNT = 4, 4, 8, 8


def least_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    lanes_in = g["batch"] * (KEY + VALUE + TS)
    state = g["campaigns"] * 2 * COUNT
    return lanes_in + 2 * state
