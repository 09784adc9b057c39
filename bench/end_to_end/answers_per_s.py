"""Answers completed in the window over the time from its start to the
last completion."""


def read(window):
    return len(window.latencies) / (window.last - window.start)
