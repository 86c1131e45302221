"""Median host time (ms) the training loop's dispatch of one step takes: the
time inside ``next()`` of ``train_on_loader`` with the device kept two steps
behind, i.e. what the host adds when the device is the bottleneck."""
from ..stats import percentile


def read(obs, q=50):
    return percentile(obs.get("enqueue_ms", ()), q)
