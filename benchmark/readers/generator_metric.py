"""Reader `generator_metric`: a number the cell's generator measured at
the client beside its end-to-end metrics (params["name"], one of the
generator's `measure()` metrics), reported as a per-layer metric: for a
client-side reading that spreads too widely from run to run to carry a
bound. The generator has no such number -> None."""


def read(params: dict, ctx):
    return ctx.generated.get(params["name"])
