"""Reader `loadgen_cpu`: CPU seconds of the harness process (all its
client threads, os.times user + system) over the window, per second of
window, as a share of one core. Near 100 %: the generator's own
interpreter sets the pace, not the server."""


def read(params: dict, ctx):
    if not ctx.window_s:
        return None
    return 100.0 * ctx.loadgen_cpu_s / ctx.window_s
