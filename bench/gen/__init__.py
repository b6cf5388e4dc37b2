"""Input generators of the chip benchmark: graphs, features, weights,
labels, training batches and request traces, all drawn from seeds."""
