"""Layer: set-up (``ES.__init__``, ``init_state``, ``compile``,
``utils.backend``): where ``setup_s`` goes.

Sources, all on ``time.perf_counter``, the clock of the runner's fences: the
program's set-up timeline (``estorch_tpu.obs.spans.TIMELINE``: the process's
start, the moment the package was imported, every ``setup/`` span whole, the
first generations' phases whole) and its acquisition log
(``estorch_tpu.utils.backend.acquisition_log``: every trace, lowering,
acquisition and cache retrieval with its name and interval).  A program
without them (the parent of the PR that brought them, or one run with
``ESTORCH_OBS=0``) gives ``{}`` and a line that says why.

The timeline is cut at ``run["fences"][0]``, the window's opening fence, and
EVERY INSTANT from the process's start to that fence is booked to exactly one
part, innermost first, as ``stage_reduce`` books a device operation to its
innermost stage:

1. inside ``setup/compile``: ``lower_s`` under ``setup/compile/lower``,
   ``acquire_s`` anywhere else in it (its own trace, lowering and acquisition
   events lie under those two spans and book with them);
2. else inside a trace, lowering or acquisition event: ``programs_s`` (every
   OTHER executable; ``programs`` counts their acquisitions);
3. else inside a set-up span: ``state_s`` where the innermost is an
   ``init_state``, ``build_s`` otherwise (``setup/init`` and its children);
4. else inside a generation phase (the warm-up's ``dispatch`` / ``device`` /
   ``host_sync`` / ``record``): ``warmup_s``;
5. else by the landmarks: before the package was imported
   ``before_program_s``, before the first ``setup/init`` ``before_init_s``,
   after the last phase that ended before the fence ``after_warmup_s``, and
   what is left ``unspanned_s``.  The runner's ``bring_up_s``, which
   ``setup_s`` leaves out, is taken out of the first of those where a jax
   backend was live when the package was imported and out of the second
   where none was (``TIMELINE.backend_up_at_import``).

The ten parts sum to ``setup_s``; the reader raises where they do not to
1e-3 s.  ``retrieval_s`` is the cache's own reads inside the acquisitions: a
part of ``acquire_s`` and ``programs_s``, not of the sum.
"""

from __future__ import annotations

import sys

PARTS = ("before_program_s", "before_init_s", "build_s", "state_s",
         "lower_s", "acquire_s", "programs_s", "warmup_s", "after_warmup_s",
         "unspanned_s")
COMPILE = "setup/compile"
INIT = "setup/init"
ACQUISITION_KINDS = ("trace", "lower", "backend")


def say(text: str) -> None:
    print(f"[boot] {text}", flush=True)


def in_compile(name: str) -> bool:
    return name == COMPILE or name.startswith(COMPILE + "/")


def book(start, imported, fence, spans, phases, events):
    """The partition of ``[start, fence]``.  ``spans`` and ``phases`` are
    ``(name, begin, end, ...)``, ``events`` ``(kind, fun_name, end, duration,
    cache_hit)``, all clipped to the interval here.  Returns the seconds of
    each of ``PARTS`` (the bring-up still inside ``before_program_s``), how
    many executables were acquired outside and inside ``setup/compile``, and
    the retrievals' seconds."""
    marks = []

    def add(begin, end, kind, key):
        begin, end = max(begin, start), min(end, fence)
        if end > begin:
            # at one instant an end sorts before a begin
            marks.append((begin, 1, kind, key))
            marks.append((end, 0, kind, key))

    for i, span in enumerate(spans):
        add(span[1], span[2], "span", i)
    for phase in phases:
        add(phase[1], phase[2], "phase", None)
    for kind, _, end, duration, _ in events:
        if kind in ACQUISITION_KINDS:
            add(end - duration, end, "event", None)
    first_init = min((s[1] for s in spans if s[0] == INIT), default=fence)
    last_phase = max((p[2] for p in phases if p[2] <= fence), default=fence)
    for landmark in (imported, first_init, last_phase):
        marks.append((min(max(landmark, start), fence), 0, None, None))
    marks.append((fence, 0, None, None))
    marks.sort(key=lambda m: (m[0], m[1]))

    open_spans: set[int] = set()
    open_count = {"event": 0, "phase": 0}

    def owner(at):
        inner = None
        if open_spans:
            # innermost: the latest to begin, the deeper name at a tie
            inner = spans[max(open_spans, key=lambda i: (
                spans[i][1], spans[i][0].count("/")))][0]
            if in_compile(inner):
                return ("lower_s" if inner == COMPILE + "/lower"
                        else "acquire_s")
        if open_count["event"]:
            return "programs_s"
        if inner is not None:
            return ("state_s" if inner.rsplit("/", 1)[-1] == "init_state"
                    else "build_s")
        if open_count["phase"]:
            return "warmup_s"
        if at < imported:
            return "before_program_s"
        if at < first_init:
            return "before_init_s"
        if at >= last_phase:
            return "after_warmup_s"
        return "unspanned_s"

    parts = dict.fromkeys(PARTS, 0.0)
    at = start
    for t, begins, kind, key in marks:
        if t > at:
            parts[owner(at)] += t - at
            at = t
        if kind == "span":
            (open_spans.add if begins else open_spans.discard)(key)
        elif kind is not None:
            open_count[kind] += 1 if begins else -1

    compiles = [(s[1], s[2]) for s in spans if s[0] == COMPILE]
    acquired = [e for e in events if e[0] == "backend" and e[2] <= fence]
    inside = sum(1 for e in acquired
                 if any(a <= e[2] <= b for a, b in compiles))
    return {
        "parts": parts,
        "programs": len(acquired) - inside,
        "programs_in_compile": inside,
        "retrieval_s": sum((e[3] for e in events
                            if e[0] == "retrieval" and e[2] <= fence), 0.0),
    }


def harness_start():
    """The process's start on ``perf_counter`` as ``benchmark/run.py`` has
    it (``setup_s`` is measured from there), or None where another caller
    runs the reader."""
    main = sys.modules.get("__main__")
    t, age = getattr(main, "T_START", None), getattr(main, "AGE_AT_START",
                                                     None)
    if isinstance(t, float) and isinstance(age, float):
        return t - age
    return None


def describe(spans, events, fence, booked, run):
    """What a run's log keeps beside the metrics: the children of
    ``setup/init``, the costliest acquisitions by name, the compile's two
    spans beside ``compile.aot_s``, the count beside ``compile``'s."""
    say(f"the timeline holds {sum(1 for s in spans if s[2] <= fence)} "
        f"set-up spans before the window and the log "
        f"{sum(1 for e in events if e[2] <= fence)} acquisition events")
    children: dict[str, float] = {}
    for name, begin, end, *_ in spans:
        if name.startswith(INIT + "/") and end <= fence:
            leaf = name[len(INIT) + 1:]
            children[leaf] = children.get(leaf, 0.0) + (end - begin)
    for leaf, seconds in children.items():
        say(f"{INIT}/{leaf}: {seconds:.3f} s, acquisitions inside included")
    by_name: dict[tuple, list] = {}
    for kind, fun_name, end, duration, hit in events:
        if kind in ACQUISITION_KINDS and end <= fence:
            entry = by_name.setdefault((kind, fun_name), [0, 0.0, 0])
            entry[0] += 1
            entry[1] += duration
            entry[2] += bool(hit)
    for (kind, fun_name), (n, seconds, hits) in sorted(
            by_name.items(), key=lambda kv: -kv[1][1])[:10]:
        say(f"acquisition {kind} {fun_name}: {seconds:.3f} s in {n} "
            f"event(s)" + (f", {hits} from the cache" if kind == "backend"
                           else ""))
    spanned = {leaf: sum(e - b for name, b, e, *_ in spans
                         if name == f"{COMPILE}/{leaf}" and e <= fence)
               for leaf in ("lower", "acquire")}
    aot = (run.get("compile") or {}).get("aot_s")
    say(f"{COMPILE}/lower {spanned['lower']:.3f} s + {COMPILE}/acquire "
        f"{spanned['acquire']:.3f} s = "
        f"{spanned['lower'] + spanned['acquire']:.3f} s; compile.aot_s "
        f"{aot if aot is None else round(aot, 3)}")
    counted = ((run.get("compile") or {}).get("setup") or {}).get("programs")
    say(f"{booked['programs']} executables acquired outside {COMPILE} + "
        f"{booked['programs_in_compile']} inside = "
        f"{booked['programs'] + booked['programs_in_compile']}; compile's "
        f"set-up snapshot counts {counted}")


def read(run):
    try:
        from estorch_tpu.obs.spans import TIMELINE
        from estorch_tpu.utils.backend import acquisition_log
    except ImportError:
        say("this program keeps no set-up timeline: nothing to read")
        return {}
    fences = run.get("fences") or []
    spans = list(TIMELINE.spans)
    if not fences or TIMELINE.imported is None or not any(
            s[0] == INIT for s in spans):
        say("no set-up span on the program's timeline (a Telemetry that is "
            "off, ESTORCH_OBS=0, records none): nothing to read")
        return {}
    fence = fences[0]
    bring_up_s = run.get("bring_up_s", 0.0)
    start = harness_start()
    if start is None:
        start = TIMELINE.process_start
    elif abs(start - TIMELINE.process_start) > 0.05:
        say(f"the harness has the process's start "
            f"{start - TIMELINE.process_start:+.3f} s from the program's "
            f"own stamp; the harness's is used")
    imported = TIMELINE.imported
    phases = list(TIMELINE.phases)
    events = acquisition_log()
    booked = book(start, imported, fence, spans, phases, events)
    parts = booked["parts"]
    # the runner's bring-up of the chips, which ``setup_s`` leaves out, lies
    # before the import where a backend was live by then (``train_runner``
    # asks for the devices first) and between the import and the first
    # ``setup/init`` where none was (``train_lm_runner`` imports the
    # package to check the configuration's names, then asks)
    home = ("before_program_s" if TIMELINE.backend_up_at_import
            else "before_init_s")
    if TIMELINE.backend_up_at_import is None or parts[home] < bring_up_s:
        say(f"the bring-up of {bring_up_s:.3f} s cannot be placed: a "
            f"backend live at the import: {TIMELINE.backend_up_at_import}; "
            f"{home[:-2]} {parts[home]:.3f} s; nothing is read")
        return {}
    parts[home] -= bring_up_s
    setup_s = fence - start - bring_up_s
    total = sum(parts.values())
    if abs(total - setup_s) > 1e-3:
        raise ValueError(f"the parts of set-up sum to {total:.6f} s, "
                         f"setup_s is {setup_s:.6f} s: {parts}")
    say(f"setup_s {setup_s:.3f} s = " + " + ".join(
        f"{name[:-2]} {parts[name]:.3f}" for name in PARTS))
    describe(spans, events, fence, booked, run)
    return {**{f"boot.{name}": parts[name] for name in PARTS},
            "boot.programs": booked["programs"],
            "boot.retrieval_s": booked["retrieval_s"]}
