"""Device operations of the busiest device per batched tick over the
samples of a job: their ops over the loop ticks they hold (the mean
over the job's programs, as ``tick_us.sweep`` takes it)."""


def read(ctx):
    samples = ctx.get("samples") or ()
    ticks = sum(s["ticks"] for s in samples)
    if not ticks:
        return None
    return sum(s["ops"] for s in samples) / ticks
