"""95th percentile, nearest rank, of the Q1 windows alone (their
round's due time to the max on the host), in s: how long the edge
path's users wait behind the offloads that share its host thread."""
import dataclasses

from harness import metric_reader


def read(run):
    lat = run.state.get("q1_lat")
    if lat is None:
        return None
    return metric_reader("q2_window_p95_s")(
        dataclasses.replace(run, records=lat))
