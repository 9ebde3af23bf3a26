"""Events fed in the window over the window's length, from its start to the
end of the final drain (host clock)."""


def read(run):
    return run.events / run.window_s if run.window_s > 0 else None
