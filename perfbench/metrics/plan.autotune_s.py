"""Seconds of set-up spent choosing the schedule: the program's own
``stencil.plan.autotune`` spans (the model ranking, or the measured tuning
and its cache), summed over the run, from the span record the program
keeps in memory (``repro.tracing.recorded()``, this process).  A plan made
again by a reader after the window (``perfbench.scopes``) is not the
run's and does not count.  A program without that record reads nothing."""
from perfbench.scopes import RELOWER


def read(cell):
    try:
        from repro import tracing
    except ImportError:
        return None
    spans = tracing.recorded()
    later = [(s.start_ns, s.end_ns) for s in spans if s.name == RELOWER]
    own = [s for s in spans if s.name == "stencil.plan.autotune"
           and not any(lo <= s.start_ns <= hi for lo, hi in later)]
    if not own:
        return None
    return sum(s.end_ns - s.start_ns for s in own) / 1e9
