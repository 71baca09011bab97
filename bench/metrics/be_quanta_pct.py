"""Scheduler: share of the engine quanta run while both classes had work
that went to the BE tenant (the plan's quantum share as it played out)."""


def read(rec):
    both = [s for s in rec.steps if s.ls_work and s.be_work and s.ran]
    if not both:
        return None
    return 100.0 * sum(s.ran == "BE" for s in both) / len(both)
