"""Chip benchmark of the serving path: data (configs/, traffic/, limits/,
peaks.json) read by one harness, with one reader per metric (metrics/),
one FLOP/byte count per layer kind (counts/) and one plain float32
reference block per layer kind (layers/)."""
