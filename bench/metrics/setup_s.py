"""Process start to the window's opening: imports, weights, compile or
cache reads, warm-up, and where the mix asks for it the first filling
of every slot (s)."""


def read(run):
    return run.setup_s
