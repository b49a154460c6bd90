"""Least bytes one staged batch of the NEXmark Q11 step program must move
(``jit_step_session`` holds the bid filter and the session operator).

Per batch: the filter reads the kind lane, the operator the bidder lane
and the timestamps, once.  Each distinct bidder of the batch is one state
row read and written (open flag, first and last event time, the count).
Each session that closes is one row written (bidder, start, end, count).
What no step has to move is left out: the sort's passes over the lanes,
the state rows of bidders the batch does not name, and the padding of the
output batch.

The saturated mix stamps one event a microsecond of event time, so a
batch of ``batch`` events spans ``batch`` usec: it sees the persons that
are live in that span (the newest ``active_people`` and those the span
adds, one in 50 events) and closes as many sessions as it opens, one a
new person."""

MODULES = r"^jit_step_session$"

KIND, BIDDER, TS = 4, 4, 8
FLAG, TIME, COUNT, KEY = 1, 8, 8, 4
PERSONS_OF_50 = 1


def least_bytes(cfg: dict) -> float:
    g, s = cfg["graph"], cfg["stream"]
    lanes_in = g["batch"] * (KIND + BIDDER + TS)
    new_persons = g["batch"] * PERSONS_OF_50 / 50
    row = FLAG + 2 * TIME + COUNT
    touched = (s["active_people"] + new_persons) * 2 * row
    closed = new_persons * (KEY + 2 * TIME + COUNT)
    return lanes_in + touched + closed
