"""95th percentile window latency above the knee, in s. There the
backlog grows all through the run, so the tail swings with the least
change: a layer's reading, not a bound."""
from harness import metric_reader

read = metric_reader("q2_window_p95_s")
