"""Least bytes one staged batch of the NEXmark Q9 step program must move
(``jit_step_join`` holds the person filter and the interval join).

Per batch: the filter reads the kind lane; the join reads the kind, the
auction id, the price (a bid's, or an auction's reserve), the length lane
and the timestamps, once.  Each auction is one state row written when it
opens and read when it closes (key, dateTime and expires, reserve, best
price / bid time / bidder, matched count).  Each auction that closes with
a qualifying bid is one row written (auction, dateTime, expires, price,
bid time, bidder, count).  What no step has to move is left out: the
sorts' passes over the lanes, the build row's other lanes, the rows of
auctions that stay open across steps, and the padding of the output
batch.

The saturated mix stamps one event a microsecond of event time and three
events in fifty are auctions that live 1-3 334 usec, so a batch opens
``batch * 3 / 50`` auctions and closes as many; every one of them is
counted as leaving a row (an upper bound on the rows, so a lower bound on
nothing: the count stays the LEAST the step must move only while most
auctions see a qualifying bid, which the reference's counts say:
``PERF.md`` section 5)."""

MODULES = r"^jit_step_join$"

KIND, KEY, PRICE, LENGTH, TS = 4, 4, 4, 4, 8
TIME, COUNT = 8, 4
AUCTIONS_OF_50 = 3


def least_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    lanes_in = g["batch"] * (KIND + KEY + PRICE + LENGTH + TS)
    auctions = g["batch"] * AUCTIONS_OF_50 / 50
    state_row = KEY + 2 * TIME + PRICE + (PRICE + TIME + KEY) + COUNT
    out_row = KEY + 2 * TIME + (PRICE + TIME + KEY) + COUNT
    return lanes_in + auctions * 2 * state_row + auctions * out_row
