"""GiB of checkpoint landed in HBM per host-clock second: bytes of every
whole restore that ended inside the window, over the time from the window's
opening to the end of the last of them (drops between restores included)."""


def read(ctx):
    restores = ctx.facts.get("restores")
    if not restores:
        return None
    return sum(b for _, _, b in restores) / 2**30 / restores[-1][1]
