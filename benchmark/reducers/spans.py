"""Per-layer metrics read from the program's own spans.

The program's ``trace_span`` observes every finished span's wall into one
histogram per name, ``span.<name>_s``, in its metrics registry, and the
kinds' counter snapshots carry every histogram's growth (sum and count are
exact; only quantiles are bucketed). So ``facts["counters_<phase>"]["hists"]``
holds, for each span name, the seconds spent under it in that phase.

``names`` are histogram names in full (``span.bert.tokenize_s``; also
``jit.persist_load_s``, which the program's compile-cache listener keeps the
same way). A name that did not occur in the phase counts 0: a phase that a
later PR takes off the path reads 0, not nothing. A reader returns ``None``
only where the phase has no ``span.`` histogram at all, which is a program
from before its spans were summed by name. ``counter`` reads a counter that
came with those spans under the same rule: 0 from such a program would say
"never" where the truth is "not counted".
"""

from __future__ import annotations

from typing import List, Optional


def _phase(facts, phase: str):
    """The phase's counter snapshot, or None where it has no ``span.``
    histogram at all."""
    got = facts.get(f"counters_{phase}") or {}
    return got if any(n.startswith("span.") for n in got.get("hists") or {}) \
        else None


def seconds(facts, phase: str, names: List[str]) -> Optional[float]:
    """Seconds spent under the named histograms in the phase, summed."""
    got = _phase(facts, phase)
    if got is None:
        return None
    return float(sum(got["hists"][n]["sum"] for n in names if n in got["hists"]))


def _wall(facts, phase: str) -> float:
    """The window's wall is the one the cell's rate was taken over; any
    other phase lasts from one counter snapshot to the next."""
    return facts["window_s"] if phase == "window" \
        else facts[f"counters_{phase}"]["seconds"]


def share_pct(facts, phase: str, names: List[str]) -> Optional[float]:
    """The same over the phase's wall."""
    s = seconds(facts, phase, names)
    return None if s is None else 100.0 * s / _wall(facts, phase)


def rest_share_pct(facts, phase: str, whole: List[str], parts: List[str]
                   ) -> Optional[float]:
    """What of ``whole`` lies under none of ``parts``, over the phase's
    wall. The parts have to lie inside the whole and beside each other for
    the rest to mean anything; it is not cut off at 0, so that parts which
    overlap show."""
    w, p = seconds(facts, phase, whole), seconds(facts, phase, parts)
    return None if w is None else 100.0 * (w - p) / _wall(facts, phase)


def counter(facts, phase: str, name: str) -> Optional[float]:
    """Growth of one of the program's counters over the phase."""
    got = _phase(facts, phase)
    return None if got is None else float(got["counters"].get(name, 0))
