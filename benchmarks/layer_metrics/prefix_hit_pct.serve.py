"""Share of the window's admitted prompt tokens whose cache rows came from
the prefix cache and were not prefilled again (the engine's counters
`prefix_hit_tokens` over `prompt_tokens`, as the job differences them over
the window)."""


def read(run):
    return run.result["counters"].get("prefix_hit_pct")
