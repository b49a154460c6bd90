"""Least bytes one staged batch of the NEXmark q16 step program must move
(``jit_step_rolling`` holds the bid filter and the rolling aggregate).

Per batch, whatever implements it: the filter reads the kind lane and the
aggregate the lanes its lift and its key read (the auction id, the
bidder, the price, the channel, the timestamp), once.  Every bid is
tested in two sets (its channel's bidders, its channel's auctions): a
4-byte word read a member tested, and the word written back.  Each
channel the batch touches is one state row read and written (the five
plain leaves as nine 32-bit words, the eight distinct counts) and one
result row (channel, the thirteen numbers as the egress takes them).
What no step has to move is left out: the sorts' passes over the lanes,
the scans, the padding of the output batch, the words of a table no bid
of the batch names, and the write of a word that did not change (the
count keeps it: a live stream sets a bit in most words it tests)."""

MODULES = r"^jit_step_rolling$"

KIND, KEY, BIDDER, PRICE, CHANNEL, TS = 4, 4, 4, 4, 4, 8
WORD = 4
SETS = 2                         # a bid's bidder, a bid's auction
BIDS_OF_50 = 46
HOT_CHANNELS, CHANNELS_NUMBER = 4, 10_000
PLAIN_WORDS, DISTINCT_COUNTS = 9, 8
ROW = 4 + 13 * 8                 # channel, thirteen int64


def channels_touched(bids: float) -> float:
    """Channels a batch of ``bids`` bids is expected to name: the hot
    four, and each cold one unless none of the cold half fell on it."""
    cold = bids / 2
    return HOT_CHANNELS + CHANNELS_NUMBER * (
        1 - (1 - 1 / CHANNELS_NUMBER) ** cold)


def least_bytes(cfg: dict) -> float:
    g = cfg["graph"]
    lanes_in = g["batch"] * (KIND + KEY + BIDDER + PRICE + CHANNEL + TS)
    bids = g["batch"] * BIDS_OF_50 / 50
    sets = bids * SETS * 2 * WORD
    touched = min(channels_touched(bids), g["max_keys"])
    state = touched * 2 * (PLAIN_WORDS + DISTINCT_COUNTS) * WORD
    return lanes_in + sets + state + touched * ROW
