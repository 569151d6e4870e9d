"""Device busy time of the busiest device per batched tick, in us, over
the samples of a job (one tick of a law's sweep program steps every
scenario of the job at once): their busy time over the loop ticks they
hold. Samples spread evenly in time give the mean over the job's
programs, each stepping ``steps`` ticks."""


def read(ctx):
    samples = ctx.get("samples") or ()
    ticks = sum(s["ticks"] for s in samples)
    if not ticks:
        return None
    return 1e6 * sum(s["busy_s"] for s in samples) / ticks
