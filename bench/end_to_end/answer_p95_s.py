"""The 95th percentile (numpy, linear) of every answer's latency in the
window, dispatch to readout."""

import numpy as np


def read(window):
    return float(np.percentile(window.latencies, 95))
