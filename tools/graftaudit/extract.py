"""The ONE optimized-HLO-text extraction path.

Every consumer of "the HLO of what the process actually compiled" goes
through this module: the audit's HLO phase (collective census / flops /
temp bytes in ``audit.analyze_program``) and ``tools/dump_hlo.py`` (the
ResNet50-224 train-step dump).  They used to re-spell the same pair —
``iter_trace_cache()`` to find the entry, ``entry.audit_lower(spec)``
to re-lower the recorded call — or worse, hand-rolled a ``.lower()``
with a fresh RNG key that compiled a program subtly different from the
one production ran.  One spelling means one set of invariants: the
audit lowering never ticks the compile counters, always lowers the
DECLARED donation (the contract under test, even where the platform
skipped it), and always describes a call that actually happened.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterator, Optional, Sequence

from . import hlo as HLO

__all__ = ["ExtractedHLO", "extract_hlo", "iter_trace_cache_hlo"]


@dataclass
class ExtractedHLO:
    """Optimized HLO text + the executable summaries every tool reads."""

    name: str
    entry: Any                       # the InstrumentedJit that owns it
    spec: Any                        # the recorded (args, kwargs) spec
    compiled: Any                    # jax.stages.Compiled
    hlo_text: str
    flops: Optional[float]
    temp_bytes: Optional[int]

    def cost_analysis(self) -> Dict[str, Any]:
        """The backend cost model's row for the executable ({} when the
        backend doesn't report one — callers print, never branch)."""
        try:
            ca = self.compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return dict(ca)
        except Exception:
            return {}


def extract_hlo(entry: Any, spec: Any,
                name: Optional[str] = None) -> ExtractedHLO:
    """Re-lower one recorded audit spec through ``entry.audit_lower``
    (fresh jit, declared donation, no counter ticks), compile it, and
    return the optimized HLO text with flops / temp-bytes attached."""
    lowered = entry.audit_lower(spec)
    compiled = HLO.compile_lowered(lowered)
    return ExtractedHLO(
        name=name or getattr(entry, "name", "<entry>"),
        entry=entry, spec=spec, compiled=compiled,
        hlo_text=compiled.as_text(),
        flops=HLO.compiled_flops(compiled),
        temp_bytes=HLO.compiled_temp_bytes(compiled))


def iter_trace_cache_hlo(kinds: Optional[Sequence[str]] = None
                         ) -> Iterator[ExtractedHLO]:
    """Extracted HLO for every recorded spec of every live trace-cache
    entry (optionally filtered to entry ``kinds``) — the in-process
    spelling the profiling tools use: whatever program the process
    really ran, re-lowered from its recorded call, never a
    hand-reconstructed approximation."""
    from deeplearning4j_tpu.nn.compile_cache import iter_trace_cache

    seen: Dict[str, int] = {}
    for _key, entry in iter_trace_cache():
        if kinds is not None and entry.name not in kinds:
            continue
        for spec in entry.audit_specs():
            i = seen.get(entry.name, 0)
            seen[entry.name] = i + 1
            yield extract_hlo(entry, spec, name=f"{entry.name}#{i}")
