"""Share of the traced window in which no op runs on the device, in %."""
import devtrace


def read(run):
    return None if run.trace is None else devtrace.idle_share(run.trace)
