"""Builder's tools: looking at a trace, sweeping a rate, recording test data.
Nothing here is imported by a run."""
