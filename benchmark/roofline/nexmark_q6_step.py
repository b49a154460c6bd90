"""Least bytes one staged batch of the NEXmark Q6 step programs must move
(both programs of a batch: ``jit_step_join`` holds the person filter and
the interval join, ``jit_step_w2`` the count window over the join's rows).

The join's count is ``nexmark_q9_step``'s, with the seller (4 B) more in
each state row and each row handed over.  Behind it, per batch: every row
the join closes is handed over once (written by the join: that count
holds it) and read once by the window; the window reads, for each row,
the pane cells its window touches (the seller's last ``window - 1`` lifted
rows: a sum and a count, int64) and the seller's row count, writes the
one cell and the count the row changes, and writes one result row
(seller, dateTime, sum, how many, final price, expires, matched bids).
What no step has to move is left out: the sorts' passes, the rows that
wait a step in the window's state (a handle would do), the cells a seller
keeps and no row of the batch touches, and the padding of either output
batch.

Every auction a batch opens is counted as closing with a qualifying bid
(``nexmark_q9_step``'s upper bound on the rows, which stays the LEAST the
step must move while most auctions see one: ``PERF.md`` section 5)."""

MODULES = r"^jit_(step_join|step_w2)$"

KIND, KEY, PRICE, LENGTH, TS = 4, 4, 4, 4, 8
TIME, COUNT, SELLER = 8, 4, 4
CELL = 8 + 8                     # a lifted row: sum and n, int64
AUCTIONS_OF_50 = 3


def join_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    lanes_in = g["batch"] * (KIND + KEY + PRICE + LENGTH + TS)
    auctions = g["batch"] * AUCTIONS_OF_50 / 50
    state_row = KEY + 2 * TIME + PRICE + SELLER \
        + (PRICE + TIME + KEY) + COUNT
    out_row = KEY + 2 * TIME + (PRICE + TIME + KEY) + SELLER + COUNT
    return lanes_in + auctions * 2 * state_row + auctions * out_row


def window_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    rows = g["batch"] * AUCTIONS_OF_50 / 50
    handed = KEY + 2 * TIME + (PRICE + TIME + KEY) + SELLER + COUNT
    touched = (g["window_rows"] - 1) * CELL + 8          # read: cells, count
    changed = CELL + 8                              # written
    result = KEY + TIME + 5 * 8
    return rows * (handed + touched + changed + result)


def least_bytes(cfg: dict) -> float:
    return join_bytes(cfg) + window_bytes(cfg)
