"""90th percentile, over the requests that ended in the window, of the
milliseconds from the instant a request was due to the scheduler giving it
a slot (`Request.admit_t`). Nothing to read where the job stamps no due
instants: in a closed loop of as many clients as slots it is zero by
construction."""


def read(run):
    return run.result["counters"].get("queue_wait_ms.p90")
