"""Leaf evaluations a second: the program's own simulation count in
each harvest (`SelfPlayResult.total_simulations`) over the window."""


def read(ctx):
    return ctx["counters"]["simulations"] / ctx["window_s"]
