"""Per-layer metrics read from what the program keeps of its own pauses
(PR 36): the collector's seconds and counts (``span.host.gc_s``,
``host.gc_collections.gen*``), the excess of its slow units
(``slow.<unit>_excess_s``), its slow device steps (``slow.train.step``) and
each span's thread-CPU seconds (``cpu.<name>_s``).

The readers are ``reducers/spans.py``'s, reached through this file for one
rule more: they give nothing where the phase holds no ``cpu.`` histogram at
all, which is a program from before it counted any of these. Without it
such a program would read 0 collections where the truth is "not counted",
and the whole of a span's wall as off the processor. A program that counts
them reads 0 for a name that did not occur: "no slow cycle" is a reading.
"""

from __future__ import annotations

from typing import List, Optional

from benchmark.reducers import spans


def _counted(facts, phase: str) -> bool:
    got = facts.get(f"counters_{phase}") or {}
    return any(n.startswith("cpu.") for n in got.get("hists") or {})


def share_pct(facts, phase: str, names: List[str]) -> Optional[float]:
    return spans.share_pct(facts, phase, names) if _counted(facts, phase) else None


def rest_share_pct(facts, phase: str, whole: List[str], parts: List[str]
                   ) -> Optional[float]:
    return spans.rest_share_pct(facts, phase, whole, parts) \
        if _counted(facts, phase) else None


def counter(facts, phase: str, name: str) -> Optional[float]:
    return spans.counter(facts, phase, name) if _counted(facts, phase) else None
