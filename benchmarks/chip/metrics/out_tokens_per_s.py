"""Output tokens delivered in the window, over the window's seconds."""

from records import tokens_in_window


def read(run):
    return len(tokens_in_window(run)) / run.seconds
