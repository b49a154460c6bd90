"""Least bytes one staged batch of the NEXmark q20 step program must move
(``jit_step_join_pairs`` holds the person filter and the pair form of the
interval join).

Per batch: the filter reads the kind lane; the join reads the kind, the
auction id, the four number lanes (a bid's bidder, price, channel, url;
an auction's seller, reserve, category, length) and the timestamps,
once.  Each auction is one table row written (its dateTime as two 32-bit
words and its four numbers).  Each pair is one row written (auction,
both dateTimes, the four numbers of each side), at the source's one
category in five: a fifth of the bids.  What no step has to move is left
out: the sorts' passes over the lanes, the lookups of the probes whose
auction is not in their batch, the probes that wait, and the padding of
the output batch.  A floor, so the share cannot read over 100 %."""

MODULES = r"^jit_step_join_pairs$"

KIND, KEY, NUMBER, TS = 4, 4, 4, 8
NUMBERS = 4                  # a side's number lanes
TIME = 8
AUCTIONS_OF_50, BIDS_OF_50, CATEGORIES = 3, 46, 5


def least_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    lanes_in = g["batch"] * (KIND + KEY + NUMBERS * NUMBER + TS)
    auctions = g["batch"] * AUCTIONS_OF_50 / 50
    pairs = g["batch"] * BIDS_OF_50 / 50 / CATEGORIES
    table_row = TIME + NUMBERS * NUMBER
    out_row = KEY + 2 * TIME + 2 * NUMBERS * NUMBER
    return lanes_in + auctions * table_row + pairs * out_row
