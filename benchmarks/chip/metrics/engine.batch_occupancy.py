"""Mean sequences per fused decode pass in the window / the cell's clients,
in %: how full the engine keeps the running batch."""


def read(run):
    dec = run.passes("decode")
    if not dec:
        return None
    return 100.0 * sum(p.batch for p in dec) / len(dec) / run.clients
