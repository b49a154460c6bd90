"""Least bytes one staged batch of the NEXmark Q5 step programs must move
(both window stages: ``jit_step`` holds the bid filter with the sliding
count, ``jit_step_w2`` the arg-max over its fired rows).

Per batch: the filter reads the kind lane, the count the auction id and
the timestamps; each auction a batch bids on is one cell read and written
(16 B; a batch spans ``batch * 3 / 50`` new auctions, and a bid goes to
those or to the hundred before them).  Per fired window, one in every
``slide`` of event time: the fold reads the window's panes of every key
and their flags once, and the second stage reads the rows it fired (an
id, a count) once.  What no step has to move is left out: the ring needs
no roll to advance (an index would do), and the panes outside the firing
window are not read."""

MODULES = r"^jit_(step|step_w2|mega)$"

KEY, KIND, TS, COUNT, FLAG = 4, 4, 8, 8, 1
AUCTIONS_OF_50 = 3


def least_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    panes = g["window_usec"] // g["slide_usec"]
    lanes_in = g["batch"] * (KEY + KIND + TS)
    placed = g["batch"] * AUCTIONS_OF_50 / 50 * 2 * COUNT
    # the saturated mix stamps one event a microsecond of event time
    batches_per_window = g["slide_usec"] / g["batch"]
    fold = g["max_keys"] * panes * (COUNT + FLAG)
    rows = g["max_keys"] * (KEY + COUNT) * 2          # written, then read
    return lanes_in + placed + (fold + rows) / batches_per_window
