"""90th percentile, over the requests that ended in the window, of the
milliseconds from the instant a request was due (the open loop's arrival
schedule; jobs/serve_open.py stamps it) to its first token. A job that
stamps no due instants (the closed loop) leaves nothing to read."""


def read(run):
    return run.result["counters"].get("ttft_from_due_ms.p90")
