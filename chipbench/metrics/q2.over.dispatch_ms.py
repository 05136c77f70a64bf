"""Host time per window above the knee, in ms: ``q2.dispatch_ms`` read
in a cell judged on ``q2_windows_per_s``."""
from harness import metric_reader

read = metric_reader("q2.dispatch_ms")
