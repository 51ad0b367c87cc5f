"""95th percentile of how late the load generator submitted requests
against their open-loop schedule, in ms: a starved generator is not a fast
server."""

import numpy as np


def read(run):
    lags = run.driver.lags_ms()
    lags = lags[np.isfinite(lags)]
    return float(np.percentile(lags, 95)) if lags.size else None
