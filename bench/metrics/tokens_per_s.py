"""Tokens of every step of the window, over the window's host-clock time,
global across chips."""


def read(run):
    return run.window.tokens / run.window.seconds
