"""The harness's start to the first timed call."""


def read(window):
    return window.setup_s
