"""Tuples pulled inside the window / time from the window's start to the
delivery of the last batch that holds a row of one of them (the rows of
the end-of-stream flush included: every tuple of a sliding window's tail
has rows there)."""


def read(trace, stats, window):
    if window["t_last_delivery"] is None:
        return None
    end = max(window["t_last_delivery"], window["t_stop"])
    return window["tuples_in_window"] / (end - window["t_open"])
