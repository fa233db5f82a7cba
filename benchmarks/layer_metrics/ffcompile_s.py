"""Seconds in FFModel.compile and serve(): PCG, search, plan, placement
(harness spans named `ffcompile`, during set-up)."""


def read(run):
    spans = run.ctx.seconds_in("ffcompile")
    return sum(spans) if spans else None
