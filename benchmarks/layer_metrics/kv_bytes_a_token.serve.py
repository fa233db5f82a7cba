"""Bytes of cache a held token costs, every layer counted: the engine's
`kv_pool_bytes` (the blocks a live slot or the prefix cache holds, in both
cache groups, at their stored size) over `kv_cached_tokens` (the rows of
the global group's held blocks), as the job reads them when the window
closes. A cache that holds every layer's rows for the whole context reads
the sum of the layers' rows (30,720 B for `mimo-v2-flash`); one whose
window layers hold the window reads the global layers' rows plus the
windows' share."""


def read(run):
    return run.result["counters"].get("kv_bytes_a_token")
