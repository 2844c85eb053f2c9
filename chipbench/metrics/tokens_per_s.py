"""Tokens received in the window, first tokens included, over the window's
seconds (host clock; the window ends with the last token it counted)."""
from chipbench.drive import window_tokens


def read(run):
    return window_tokens(run.window) / run.window_s
