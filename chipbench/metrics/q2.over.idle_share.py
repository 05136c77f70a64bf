"""Device idle share above the knee, in %: ``q2.idle_share`` read in a
cell judged on ``q2_windows_per_s``."""
from harness import metric_reader

read = metric_reader("q2.idle_share")
