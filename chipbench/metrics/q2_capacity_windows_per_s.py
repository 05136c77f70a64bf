"""Windows completed per second with the offered load above the knee:
the offload path's capacity. The backlog grows all through the run, so
the window's seconds run from the first slide's due time to the last
result, and every window issued in them is counted."""
from harness import metric_reader

read = metric_reader("q2_windows_per_s")
