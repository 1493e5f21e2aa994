"""What the program writes into a profiler trace, beside what
:mod:`bench.trace_reduce` reads of it.

Two things, read from the same ``.xplane.pb``:

* the program's host spans (``serving.*`` and ``engine.*``, opened by
  ``repro.obs`` on the profiler's clock) with their arguments;
* each device op's name scope: the ``tf_op`` stat of the op's event
  metadata, such as ``jit(stablelm_1_6b_paged_decode)/layers/while/body/
  closed_call/paged_decode_attention/gather``.  ``jax.profiler.ProfileData``
  does not expose event metadata, so :func:`scopes` parses the XSpace
  protobuf with the schema TensorFlow ships, and joins it to the ops on
  their full HLO name.

From them it computes what three per-layer metrics would read
(:func:`tick_host_ms`, :func:`decode_attn_site_ms`,
:func:`layer_state_ms`) and names the device's idle gaps by the innermost
span, the benchmark's or the program's, that covers each.  The harness's
reader (:func:`bench.trace_reduce.load`) keeps neither, so no result line
reports these yet: ``bench/program_readings.py`` runs a cell and prints
them (:func:`readings`) beside its result line.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import importlib.util
import pathlib
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from bench import trace_reduce as TR

#: Name prefixes of the program's spans (the benchmark's own are in
#: ``trace_reduce.HOST_SPANS``).
PROGRAM = ("serving.", "engine.")
#: Ops of the decode step program's attention sites.
DECODE_ATTN = re.compile(r"^jit\([^)]*_paged_decode\)/.*"
                         r"/paged_decode_attention/")
#: Ops of the layer scan itself: in ``layers`` and its loop, but not in
#: the loop body's call (``.../while/body/closed_call/...``), which holds
#: the layer's own work.
LAYER_SCAN = re.compile(r"^jit\([^)]*\)/layers(/while(/body|/cond)?)?"
                        r"/[^/]+$")

Span = Tuple[str, int, int, Dict[str, Any]]


@dataclasses.dataclass
class ProgramTrace:
    """A reduced trace with each op's scope (``""`` where the trace has
    none), parallel to ``trace.ops``, and the program's spans in the
    stretch, in nanoseconds on the trace's clock."""

    trace: TR.Trace
    scopes: List[List[str]]
    spans: List[Span]

    def ticks(self, phase: Optional[str] = None) -> List[Span]:
        """The ``serving.tick.<phase>`` spans (any phase if None) that
        start in the stretch."""
        want = f"serving.tick.{phase}" if phase else "serving.tick."
        return [s for s in self.spans if s[0].startswith(want)
                and s[1] >= self.trace.lo]

    def self_seconds(self, keep) -> float:
        """Device seconds of their own (a loop less the ops inside it) of
        the ops whose scope ``keep(scope)`` accepts, averaged over
        devices."""
        if not self.trace.ops:
            return 0.0
        total = 0
        for dev, scopes in zip(self.trace.ops, self.scopes):
            scope_of = {name: sc for (name, _, _), sc in zip(dev, scopes)}
            total += sum(t for name, t in TR.self_times(dev)
                         if keep(scope_of[name]))
        return total / len(self.trace.ops) * 1e-9

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds on device 0 by the innermost benchmark or program
        span covering each gap's middle, largest first."""
        spans = self.trace.spans + [s[:3] for s in self.spans]
        return dataclasses.replace(self.trace, spans=spans).idle_gaps(n)


def load(path: str) -> ProgramTrace:
    trace = TR.load(path)
    scope_of = scopes(path)
    return ProgramTrace(
        trace=trace,
        scopes=[[scope_of.get(name, "") for name, _, _ in dev]
                for dev in trace.ops],
        spans=program_spans(path, trace.lo, trace.hi))


def program_spans(path: str, lo: int, hi: int) -> List[Span]:
    """The program's host spans that overlap ``[lo, hi)``, with args."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM) and name not in TR.HOST_SPANS:
                    s = int(ev.start_ns)
                    e = int(ev.start_ns + ev.duration_ns)
                    if e > lo and s < hi:
                        out.append((name, s, e, dict(ev.stats)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


# ---------------------------------------------------------------- metrics
def tick_host_ms(pt: ProgramTrace) -> Optional[float]:
    """Median over the traced ticks of the host time in which the chip
    waits on the host: the ``serving.tick.*`` span less its
    ``serving.fetch`` child, plus the ``serving.admit`` span before it."""
    admits = [s for s in pt.spans if s[0] == "serving.admit"]
    fetches = [s for s in pt.spans if s[0] == "serving.fetch"]
    out = []
    for _, s, e, _ in pt.ticks():
        fetch = sum(fe - fs for _, fs, fe, _ in fetches
                    if s <= fs and fe <= e)
        before = [a for a in admits if a[2] <= s]
        admit = before[-1][2] - before[-1][1] if before else 0
        out.append(e - s - fetch + admit)
    return statistics.median(out) * 1e-6 if out else None


def decode_attn_site_ms(pt: ProgramTrace) -> Optional[float]:
    """Device ms per traced decode tick of the ops that the decode step
    program runs for its ``paged_decode_attention`` sites: the page
    gather, the re-layouts and the kernel."""
    return _per_tick_ms(pt, DECODE_ATTN, pt.ticks("decode"))


def layer_state_ms(pt: ProgramTrace) -> Optional[float]:
    """Device ms per traced tick of the layer scan's own ops: the slicing
    of each layer's weights and state out of the stacked arrays and the
    stacking of the new state."""
    return _per_tick_ms(pt, LAYER_SCAN, pt.ticks())


def idle_in_step_named_share(pt: ProgramTrace) -> Optional[float]:
    """Share of the idle seconds inside the benchmark's ``engine.step``
    that a program span below it names."""
    in_step = dict(pt.trace.idle_gaps(None)).get("engine.step")
    if not in_step:
        return None
    named = sum(t for name, t in pt.idle_gaps(None)
                if name.startswith(PROGRAM))
    return named / in_step


def readings(pt: ProgramTrace) -> Dict[str, Any]:
    """Everything this module reads of one traced stretch, as one JSON
    object."""
    ticks = collections.Counter(s[0].rsplit(".", 1)[1] for s in pt.ticks())
    return {"tick_host_ms": tick_host_ms(pt),
            "decode_attn_site_ms": decode_attn_site_ms(pt),
            "layer_state_ms": layer_state_ms(pt),
            "idle_gaps": pt.idle_gaps(None),
            "idle_in_step_named_share": idle_in_step_named_share(pt),
            "ticks": dict(ticks),
            "ops_without_scope_s": pt.self_seconds(lambda sc: not sc),
            "busy_s": pt.trace.busy_s, "window_s": pt.trace.window_s}


def _per_tick_ms(pt: ProgramTrace, rx: re.Pattern,
                 ticks: List[Span]) -> Optional[float]:
    if not ticks or not any(any(dev) for dev in pt.scopes):
        return None
    return pt.self_seconds(lambda sc: bool(rx.match(sc))) / len(ticks) * 1e3


# ------------------------------------------------------------- XSpace
def scopes(path: str) -> Dict[str, str]:
    """``tf_op`` of every device op's event metadata, keyed by the
    metadata's name (the op's full HLO text, as ``ProfileData`` names the
    op's events), from the device planes of an ``.xplane.pb``; empty where
    the XSpace schema cannot be loaded."""
    xplane_pb2 = _xplane_pb2()
    if xplane_pb2 is None:
        return {}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            out.update(_plane_scopes(plane))
    return out


def _plane_scopes(plane) -> Dict[str, str]:
    stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
    by_name, by_display, dedup = {}, {}, {}
    for em in plane.event_metadata.values():
        stats = {stat_names.get(st.metadata_id): st.str_value
                 for st in em.stats if st.HasField("str_value")}
        if "tf_op" in stats:
            op = stats["tf_op"].rstrip(":")
            by_name[em.name] = by_display[em.display_name] = op
        elif "deduplicated_name" in stats:
            # An op XLA deduplicated carries the other's metadata name.
            dedup[em.name] = stats["deduplicated_name"]
    for ev_name, other in dedup.items():
        if other in by_display:
            by_name[ev_name] = by_display[other]
    return by_name


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    """The XSpace schema TensorFlow ships (``tsl/profiler/protobuf``),
    loaded from its file so that TensorFlow itself is never imported;
    None where TensorFlow or ``google.protobuf`` is not installed."""
    try:
        tf = importlib.util.find_spec("tensorflow")
    except (ImportError, ValueError):
        return None
    if tf is None or not tf.submodule_search_locations:
        return None
    path = pathlib.Path(tf.submodule_search_locations[0],
                        "tsl", "profiler", "protobuf", "xplane_pb2.py")
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(module)
    except ImportError:
        return None
    return module
