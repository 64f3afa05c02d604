"""Edges of all loads of the window over the time from the window's
start to the end of the last load."""


def read(w):
    if not w.ops:
        return None
    return sum(u for _s, _e, u in w.ops) / (w.ops[-1][1] - w.start)
