"""Fragment launches thrown away in set-up: the launches of mesh-tier
fragment programs (FRAGMENT_DISPATCH, every kind: see
``fragment_launches_per_stmt``) while the connections warmed, less the
warm statements (one fragment each in the cells that list this metric).
Above 0, a new connection launched an under-sized program first, threw
its device work away and compiled and launched again: the connection
learning its capacities. Source: program counter."""


def read(ctx):
    n = [v for k, v in ctx.setup_counters.items() if k.startswith("fragment:")]
    if not n or not ctx.warm:
        return None
    return sum(n) - len(ctx.warm)
