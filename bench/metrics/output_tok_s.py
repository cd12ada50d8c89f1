"""Output tokens the host received in the window, over the window."""


def read(run):
    return run.tokens_in_window() / run.seconds
