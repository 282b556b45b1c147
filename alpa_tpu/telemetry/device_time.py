"""Device time by part of the model: the reduction of a profiler trace
that :func:`alpa_tpu.telemetry.trace.stop_capture` keeps on its
:class:`~alpa_tpu.telemetry.trace.Capture` (``Capture.device_time()``).

On the TPU a device event carries no scope: an event of the line ``XLA
Ops`` is named by its HLO instruction and an event of ``XLA Modules`` by
the program (``jit_decode(<fingerprint>)``).  The compiled program's HLO
text does carry each instruction's place in the model, as the ``op_name``
of its ``metadata``: the ``jax.named_scope`` path it was traced under,
which holds every flax module's name
(``jit(decode)/GPTModel/h0/attn/qkv/dot_general``).  Three pieces, from the
text to the table:

**Parts** (:func:`part_of`).  The components of an ``op_name`` path are
looked up in one table, the innermost match winning; a component counts
bare or wrapped (``transpose(jvp(attention))``, and below ``checkpoint`` /
``rematted_computation``); an argument's name, which a copy the compiler
makes of a weight keeps, counts by the keys of its place in the arguments'
tree (``params['params']['h0']['attn']['q_b']['kernel']``):

===========================  ==========================================
part                         components
===========================  ==========================================
``embed``                    ``wte``, ``wpe`` (the tables' rows; not
                             ``wte.attend``)
``norm``                     ``ln1``, ``ln2``, ``ln_f``, ``*_post``,
                             ``*_norm`` (``q_norm``, ``k_norm``,
                             ``q_a_norm``, ``kv_a_norm``)
``projection``               ``attn`` outside its core: ``attn/qkv``,
                             ``attn/out``, ``attn/gate``, the low-rank
                             ``q_a`` / ``q_b`` / ``kv_a`` of
                             ``LatentAttention``, rotary positions
``attention``                ``model.gpt_model.ATTENTION_SCOPE``: scores,
                             softmax, values
``attention.cache_write``    ``cache_write`` inside it: the step's keys
                             and values written into the cache
``attention.indexer``        ``indexer`` inside it: of a latent layer that
                             selects its positions, the indexer's
                             projections, scores and choice
``attention.latent_select``  ``latent_select`` inside it: the selected
                             positions' gather and the core over them
``attention.window_core``    ``window_core`` inside it: a window layer's
                             core (its ring, the softmax with its sink)
                             where the window and the full layers differ
                             in more than the mask
``attention.full_core``      ``full_core`` inside it: there, a full
                             layer's core over its cache
``short_conv``               ``model.gpt_model.CONV_SCOPE``, a gated short
                             convolution whole: both products, the gates,
                             the taps, the state's update (and ``conv``,
                             its module: a weight the compiler copies)
``ssm_mixer``                ``model.gpt_model.SSM_SCOPE``, a Mamba-2 or a
                             Mamba-1 mixer whole: the projections, the
                             convolution, the recurrence, the gate (and
                             norm), both states' updates (and ``ssm``, its
                             module: a weight the compiler copies)
``ssm_mixer.scan``           ``model.gpt_model.S6_SCAN_SCOPE``, a Mamba-1
                             mixer's recurrence ALONE (the step of a tick,
                             the walk over a chunk's positions)
``attention.summaries``      ``model.gpt_model.EVA_SCOPE``, inside the
                             attention core of an "eva" layer: the pooling
                             of chunks of keys and values into summaries
                             and the write of the pooled rows
``mlp``                      ``mlp`` (``MLPBlock``; a routed layer's
                             shared expert)
``moe``                      ``model.moe.SCOPE``: router, top-k, sort,
                             dispatch, activation, combine
``moe.grouped_matmul``       ``ops.grouped_matmul.SCOPE`` inside it
``mtp``                      ``model.gpt_model.MTP_SCOPE``: a multi-token-
                             prediction module's own work (``enorm``,
                             ``hnorm``, ``eh_proj``); its block and its
                             pass through the head lie further in and
                             keep their parts
``head``                     ``wte.attend``, ``lm_head``
``loss``                     ``loss`` (``model_util``'s loss functions)
``block``                    ``h<i>`` and nothing further in: the
                             residual additions
``collective``               by opcode, whatever the path: ``all-gather``,
                             ``all-reduce``, ``reduce-scatter``,
                             ``collective-permute``, ``all-to-all`` and
                             their ``-start`` / ``-done``
``outside_model``            an ``op_name`` under none of these (the
                             optimizer's update)
``unscoped``                 no ``op_name``, and no user that has one
===========================  ==========================================

**Fusions** (:func:`instruction_parts`).  A fusion carries its root's
``op_name``, and the root is often not where the time goes (a matmul fused
with the next LayerNorm's statistics).  So a fusion is read from the
instructions of its fused computation: if they hold a ``dot``, a
``convolution`` or a Pallas ``custom-call``, the fusion is that
instruction's part (of several, the one with the largest operand);
otherwise a collective's, if it holds one; otherwise its root's.  A fusion
whose instructions lie under more than one part is *mixed*: its seconds go
to the one part all the same, and are also added to the program's
``mixed_s``, the share of the table that rests on this rule.

**What the compiler made itself** has no ``op_name``: a prefetch's
``copy-start`` / ``copy-done`` (the time a program waits for a weight it
streams ahead), a ``slice-start`` / ``slice-done``, a ``ConcatBitcast``, a
copy that re-lays a cache out for the product that reads it, the SPMD
partitioner's copies on a mesh.  Such an instruction works for whoever
uses its result, and takes that instruction's part (the first user that
has one, through other unnamed instructions); its seconds are also added
to the program's ``inherited_s``.  Only what no user names stays
``unscoped`` (7 % of a Trinity decode and 13 % of a pipeshard stage would
be, left there).

**The registry** (:func:`register_program`).  Whatever compiles a program
for a device says, under the name the profiler gives its runs, how its
optimised HLO text is got.  Written when a program compiles, read only when
a capture's table is made; a program that registered nothing is reduced
by program only and all its time is ``unscoped``.  Programs that share a
name are all kept, and the one that knows a run's instructions is taken.
The registry holds its owners weakly.  A pipeshard executable also says,
when it lowers its register program, what lays a step's RUN ops over the
device events (:func:`register_pipeline`; ``telemetry/perf.py`` makes the
join).

jax's persistent compilation cache leaves metadata out of its key, so a
program read back from a cache that an earlier tree filled keeps that
tree's ``op_name``s: a scope added since does not show until the cache is
cold.  ``unscoped_s`` and ``outside_model`` show such a program for what
it is.

Needs ``jax.profiler.ProfileData`` (in :func:`read_profile` alone) and
``re``, nothing else.
"""
import re
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "PARTS", "UNSCOPED", "OUTSIDE_MODEL", "MIXED", "INHERITED", "part_of",
    "instruction_parts",
    "register_program", "registered_parts", "registered_texts",
    "compiled_name",
    "register_pipeline", "registered_pipelines",
    "read_profile", "reduce_events", "part_seconds", "empty_table",
]

UNSCOPED = "unscoped"
OUTSIDE_MODEL = "outside_model"
COLLECTIVE = "collective"
# how an instruction came by its part, where not by its own op_name
MIXED, INHERITED = "mixed", "inherited"

# path component -> part.  The scope names are those of model/gpt_model.py
# (ATTENTION_SCOPE, CACHE_WRITE_SCOPE, CONV_SCOPE, SSM_SCOPE), model/moe.py
# (SCOPE),
# ops/grouped_matmul.py (SCOPE) and model/model_util.py (LOSS_SCOPE):
# telemetry imports no model.
_COMPONENTS = {
    "wte": "embed", "wpe": "embed",
    "ln1": "norm", "ln2": "norm", "ln_f": "norm",
    "attn": "projection",
    "attention": "attention",
    "cache_write": "attention.cache_write",
    # model/gpt_model.py INDEXER_SCOPE, SELECT_SCOPE: inside the attention
    # core of a latent layer that selects its positions
    "indexer": "attention.indexer",
    "latent_select": "attention.latent_select",
    # model/gpt_model.py WINDOW_CORE_SCOPE, FULL_CORE_SCOPE: the cores of
    # the two kinds of layer where they differ in more than the mask
    "window_core": "attention.window_core",
    "full_core": "attention.full_core",
    "short_conv": "short_conv", "conv": "short_conv",
    # model/gpt_model.py SSM_SCOPE, and ``ssm``, the module's name
    "ssm_mixer": "ssm_mixer", "ssm": "ssm_mixer",
    # model/gpt_model.py S6_SCAN_SCOPE: a Mamba-1 mixer's recurrence alone,
    # inside SSM_SCOPE
    "selective_scan": "ssm_mixer.scan",
    # model/gpt_model.py EVA_SCOPE: the pooling of an "eva" layer's chunks
    # into summaries and the write of the pooled rows, inside
    # ATTENTION_SCOPE
    "eva_summaries": "attention.summaries",
    "mlp": "mlp",
    "moe": "moe",
    "grouped_matmul": "moe.grouped_matmul",
    "wte.attend": "head", "lm_head": "head",
    "loss": "loss",
    # serve/generation.py UNMASK_SCOPE: a block step's choice of what it
    # unmasks (the draw, the confidences, the rule)
    "unmask": "unmask",
    # model/gpt_model.py MTP_SCOPE: a multi-token-prediction module's own
    # work, the joining of the next token's embedding with the hidden
    # state; its block's attention, experts and norms and its pass through
    # the head lie further in and keep their parts
    "mtp": "mtp", "enorm": "mtp", "hnorm": "mtp", "eh_proj": "mtp",
}
_PATTERNS = (
    (re.compile(r".+_(?:post|norm)$"), "norm"),
    (re.compile(r"h\d+$"), "block"),
)
PARTS = tuple(dict.fromkeys(
    list(_COMPONENTS.values()) + [p for _, p in _PATTERNS] +
    [COLLECTIVE, OUTSIDE_MODEL, UNSCOPED]))

_COLLECTIVE_BASES = ("all-gather", "all-reduce", "reduce-scatter",
                     "collective-permute", "all-to-all")
_COLLECTIVES = frozenset(base + suffix for base in _COLLECTIVE_BASES
                         for suffix in ("", "-start", "-done"))
# an asynchronous collective's two halves, by the name their events carry
# (the TPU compiler calls a wrapped one ``async-collective-start.<n>``):
# groups (kind, ``start`` or ``done``, number)
ASYNC_COLLECTIVE = re.compile(
    "^(async-collective|" + "|".join(_COLLECTIVE_BASES) +
    r")-(start|done)((?:\.\d+)*)$")
_HEAVY = ("dot", "convolution")
_PALLAS = 'custom_call_target="tpu_custom_call"'

# ``transpose(jvp(attention))`` -> ``attention``; ``jit(decode)`` -> ``decode``
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()*([^()]*)\)*$")
# an argument's name is its place in the arguments' tree, and a copy the
# compiler makes of it (a weight re-laid out) keeps that name:
# ``params['params']['h0']['attn']['q_b']['kernel']`` -> h0, attn, q_b
_TREE_KEY = re.compile(r"\[\\?'([^'\\\]]*)\\?'\]")


def part_of(op_name: Optional[str]) -> str:
    """The part of the model an instruction's ``op_name`` lies under."""
    if not op_name:
        return UNSCOPED
    if "/" in op_name:
        bare = [_WRAPPED.sub(r"\1", c) for c in op_name.split("/")]
    else:
        bare = _TREE_KEY.findall(op_name) or [op_name]
    for component in reversed(bare):
        if component in _COMPONENTS:
            return _COMPONENTS[component]
        for pattern, part in _PATTERNS:
            if pattern.match(component):
                return part
    return OUTSIDE_MODEL


# ---- the HLO text -----------------------------------------------------

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_NAMED = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"[a-z]+(\d+)[a-z0-9]*\[([\d,]*)\]")


def _bits(hlo_type: str) -> int:
    """Bits of the arrays of an HLO type (``bf16[4,2048]{1,0}``, a tuple
    of them): enough to tell a weight from a vector."""
    total = 0
    for width, dims in _ARRAY.findall(hlo_type):
        n = int(width)
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _split(rest: str) -> Tuple[str, str, str]:
    """(result type, opcode, what follows the opcode's bracket) of an
    instruction's text after ``name = ``."""
    if rest.startswith("("):        # a tuple type: to its closing bracket
        depth = 0
        for at, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        hlo_type, rest = rest[:at + 1], rest[at + 1:]
    else:
        hlo_type, _, rest = rest.partition(" ")
    m = _OPCODE.match(rest)
    if m is None:
        return hlo_type, "", ""
    return hlo_type, m.group(1), rest[m.end():]


def _computations(hlo_text: str) -> Dict[str, List[dict]]:
    """{computation: its instructions}, each instruction ``{"name", "root",
    "type", "opcode", "op_name", "calls", "operands", "pallas"}``."""
    found, current = {}, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m:
                current = found.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _NAMED.match(line)
        if not m:
            continue
        hlo_type, opcode, tail = _split(m.group(3))
        # the operands end where the attributes begin
        operands, _, attributes = tail.partition("), ")
        name = _OP_NAME.search(attributes)
        calls = _CALLS.search(attributes)
        current.append({
            "name": m.group(2), "root": bool(m.group(1)), "type": hlo_type,
            "opcode": opcode, "op_name": name.group(1) if name else None,
            "calls": calls.group(1) if calls else None,
            "operands": _OPERAND.findall(operands),
            "pallas": opcode == "custom-call" and _PALLAS in tail})
    return found


def _fused_instructions(body: List[dict], computations: dict) -> list:
    """(instruction, result types by name of its computation) of a fused
    computation, a fusion inside it (the TPU compiler nests them) replaced
    by the instructions of its own."""
    types = {i["name"]: i["type"] for i in body}
    found = []
    for i in body:
        if i["opcode"] == "fusion" and i["calls"] in computations:
            found += _fused_instructions(computations[i["calls"]],
                                         computations)
        else:
            found.append((i, types))
    return found


def _fusion_part(fusion: dict, computations: dict) -> Tuple[str, Any]:
    """(part, how) of a fusion from the instructions of its fused
    computation."""
    body = computations[fusion["calls"]]
    inside = _fused_instructions(body, computations)
    named = [(i, types) for i, types in inside
             if i["op_name"] and i["opcode"] != "parameter"]
    parts = {part_of(i["op_name"]) for i, _ in named}
    if any(i["opcode"] in _COLLECTIVES for i, _ in inside):
        parts.add(COLLECTIVE)
    heavy = [(i, types) for i, types in named
             if i["opcode"] in _HEAVY or i["pallas"]]
    if heavy:
        decides, _ = max(heavy, key=lambda it: max(
            (_bits(it[1].get(o, "")) for o in it[0]["operands"]),
            default=0))
        part = part_of(decides["op_name"])
    elif COLLECTIVE in parts:
        part = COLLECTIVE
    else:
        root = next((i for i in body if i["root"]), None)
        part = part_of((root and root["op_name"]) or fusion["op_name"])
    return part, MIXED if len(parts | {part}) > 1 else None


def instruction_parts(hlo_text: str) -> Dict[str, Tuple[str, Any]]:
    """{instruction name: (part, how)} for every instruction of every
    computation of a compiled program's HLO text that a device event can
    be named after (the instructions inside a fused computation are not:
    the device runs the fusion).  ``how`` is None, ``MIXED`` for a fusion
    whose instructions lie under more than one part, or ``INHERITED`` for
    an instruction with no ``op_name`` that took the part of the one that
    uses its result."""
    computations = _computations(hlo_text)
    fused = {i["calls"] for body in computations.values() for i in body
             if i["opcode"] == "fusion" and i["calls"]}
    parts = {}
    for name, body in computations.items():
        if name in fused:
            continue
        users: Dict[str, List[str]] = {}    # in the program's order
        for i in body:
            if i["opcode"] in _COLLECTIVES:
                parts[i["name"]] = (COLLECTIVE, None)
            elif i["opcode"] == "fusion" and i["calls"] in computations:
                parts[i["name"]] = _fusion_part(i, computations)
            else:
                parts[i["name"]] = (part_of(i["op_name"]), None)
            for operand in i["operands"]:
                users.setdefault(operand, []).append(i["name"])
        # what the compiler made itself (a prefetch's copy-start and
        # copy-done, a slice, a re-laid-out copy) works for whoever uses
        # it: the first user that has a part, through unnamed ones
        for i in reversed(body):
            if parts[i["name"]][0] != UNSCOPED or \
                    i["opcode"] == "parameter":
                continue
            for user in users.get(i["name"], ()):
                if parts[user][0] != UNSCOPED:
                    parts[i["name"]] = (parts[user][0], INHERITED)
                    break
    return parts


# ---- the registry: program name -> ways to its HLO text ------------------

# name -> {(id of the owner, variant): [weak reference to the owner,
#                                       text(owner), parts once read]}
_PROGRAMS: Dict[str, Dict[tuple, list]] = {}


def register_program(name: str, owner: Any, hlo_text: Callable[[Any], str],
                     variant: str = "") -> None:
    """``hlo_text(owner)`` is the optimised HLO text of a program whose
    runs the profiler labels ``name``.  Called when the program compiles.
    Several programs may share a name (a train step and the program that
    creates its state are both ``jit_flat_fun``; a jitted function
    compiles once a shape, ``variant`` telling those apart): all are kept,
    and a capture takes for each program that ran the one that knows its
    instructions.  ``owner`` (the executable, the jitted function) is held
    weakly: a program that is gone is reduced as one that never
    registered."""
    _PROGRAMS.setdefault(name, {})[id(owner), variant] = [
        weakref.ref(owner), hlo_text, None]


def registered_parts(name: str) -> List[Dict[str, Tuple[str, Any]]]:
    """:func:`instruction_parts` of every live program registered as
    ``name``, each read once and kept; none for a name that registered
    nothing, whose owners are gone or whose text cannot be got."""
    found = []
    entries = _PROGRAMS.get(name, {})
    for key, entry in list(entries.items()):
        owner = entry[0]()
        if owner is None:
            del entries[key]
            continue
        if entry[2] is None:
            try:
                entry[2] = instruction_parts(entry[1](owner))
            except Exception:  # pylint: disable=broad-except
                # a reduction never fails for one program's text
                continue
        found.append(entry[2])
    return found


def registered_texts(name: str):
    """The optimised HLO text of every live program registered as
    ``name``, one after the other in the order they registered (a jitted
    function's shapes in the order it met them), each got only when it is
    asked for; a program whose text cannot be got is left out.  For a
    reader that sums a program's device events by a scope of its own
    (``chipbench``'s drivers) and has no handle on what compiled it."""
    for entry in list(_PROGRAMS.get(name, {}).values()):
        owner = entry[0]()
        if owner is None:
            continue
        try:
            yield entry[1](owner)
        except Exception:  # pylint: disable=broad-except
            continue


# [weak reference to a pipeshard executable, describe(executable)]
_PIPELINES: List[list] = []


def register_pipeline(owner: Any, describe: Callable[[Any], Any]) -> None:
    """``describe(owner)`` is what a capture needs to lay a pipeshard
    step's RUN ops over the device events (``telemetry/perf.py``
    ``joined_from_capture``): ``{"program": the lowered program's hooks,
    op_meta and dataflow graph, "mesh_chips": {mesh: chip ids},
    "run_programs": {RUN op's name: its program's name}}``, or None while
    there is nothing to describe.  Called when the executable lowers its
    register program; ``owner`` is held weakly."""
    if not any(entry[0]() is owner for entry in _PIPELINES):
        _PIPELINES.append([weakref.ref(owner), describe])


def registered_pipelines() -> List[Any]:
    """What every live registered pipeline describes now: asked when a
    capture stops (by then the benchmark's readers hold no executable),
    it only gathers references."""
    found = []
    for entry in list(_PIPELINES):
        owner = entry[0]()
        if owner is None:
            _PIPELINES.remove(entry)
            continue
        described = entry[1](owner)
        if described is not None:
            found.append(described)
    return found


def compiled_name(compiled) -> str:
    """The name the profiler gives the runs of a ``jax.stages.Compiled``:
    its HLO module's (``jit_train_step``)."""
    return compiled.runtime_executable().hlo_modules()[0].name


# ---- the trace ---------------------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def read_profile(path: str, marker: str):
    """One read of a ``.xplane.pb``: ``(marker, chips)``.  ``marker`` is
    the (start_ns, end_ns) of the host event named ``marker``, or None;
    ``chips`` is ``{chip: (ops, runs)}`` from every TPU plane: ``ops`` the
    events of ``XLA Ops`` as (instruction name, start_ns, end_ns), ``runs``
    those of ``XLA Modules`` as (program, start_ns, end_ns), the program
    without its fingerprint."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    found, chips = None, {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, runs = [], []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    for e in line.events:
                        start = e.start_ns
                        ops.append((
                            e.name.partition(" = ")[0].lstrip("%"), start,
                            start + e.duration_ns))
                elif line.name == _MODULES_LINE:
                    for e in line.events:
                        start = e.start_ns
                        runs.append((_FINGERPRINT.sub("", e.name), start,
                                     start + e.duration_ns))
            chips[int(m.group(1))] = (ops, runs)
        elif plane.name == _HOST_PLANE and found is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == marker:
                        found = (e.start_ns, e.start_ns + e.duration_ns)
                        break
                if found:
                    break
    return found, chips


def empty_table(window_ns=None) -> dict:
    return {"programs": {}, "busy_s": {}, "collectives": {},
            "window_us": None if window_ns is None else
            (window_ns[0] / 1e3, window_ns[1] / 1e3)}


def _busy_ns(ops, lo, hi) -> float:
    """Nanoseconds of [lo, hi] that some event of ``ops`` covers."""
    total, end = 0.0, lo
    for _name, s, e in sorted(ops, key=lambda op: op[1]):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def reduce_events(chips: Dict[int, tuple], window_ns=None,
                  parts_of: Callable[[str], List[dict]] = registered_parts
                  ) -> dict:
    """The table of :meth:`Capture.device_time` from what
    :func:`read_profile` gives::

        {"programs": {chip: {program: {
             "runs": n, "run_s": [seconds of each run, in order],
             "run_us": [(start, end) of each run, in the same order],
             "parts": {part: seconds}, "mixed_s": s, "inherited_s": s,
             "unscoped_s": s}}},
         "busy_s": {chip: seconds},
         "collectives": {chip: [(instruction, start, end), in order]},
         "window_us": (lo, hi)}

    The window is ``window_ns`` (the capture's marker) or, without one,
    from the first device event to the last; ``window_us`` gives it on the
    profiler's clock.  ``busy_s`` is the time of the window some operation
    of the chip covers.  A program's table holds its runs that lie wholly
    inside the window (a run the window cuts is in ``busy_s`` alone), and
    beside each run's seconds the instants it began and ended at, in
    microseconds of the profiler's clock (``run_us``: what
    ``telemetry/perf.py`` ``joined_from_capture`` gives a pipeshard step's
    RUN ops).  ``collectives`` holds, by chip, every event of those runs
    whose instruction is a collective (by opcode, or a fusion that holds
    one: the part ``collective`` less what it inherits, a prefetch's
    ``copy-done`` whose user is one), on the same clock: the intervals the
    op line spends in one.  An operation belongs to the run that contains
    it; one that holds others (a ``while``, a ``conditional`` and the
    instructions of their bodies) keeps its own time only, so that every
    instant is counted once.
    ``parts`` holds every second of the program's operations, those that
    found no name (or of a program that registered no text) under
    ``unscoped``, which ``unscoped_s`` repeats; ``mixed_s`` is the part of
    them that fusions spanning several parts account for, ``inherited_s``
    that of instructions with no ``op_name`` of their own, counted with
    the part of the instruction that uses them.
    ``parts_of(program)`` gives :func:`instruction_parts` of every program
    registered under the name.  Runs that hold the same instructions are
    reduced together, by the registered program that accounts for most of
    their seconds (of two that account for the same, the one with fewer
    instructions besides): where one jitted function ran as two programs
    (an engine's dense prefill at two buckets, both ``jit_prefill``), each
    run is read by its own, and the entry's ``runs``, ``run_s`` and
    ``parts`` hold both."""
    if window_ns is None:
        times = [t for ops, _ in chips.values() for _n, s, e in ops
                 for t in (s, e)]
        if not times:
            return empty_table()
        window_ns = (min(times), max(times))
    lo, hi = window_ns
    table = empty_table(window_ns)
    for chip, (ops, runs) in sorted(chips.items()):
        table["busy_s"][chip] = _busy_ns(ops, lo, hi) / 1e9
        programs = table["programs"][chip] = {}
        # program: the instructions a run held: instruction: ns
        own_ns: Dict[str, Dict[frozenset, Dict[str, float]]] = {}
        # (program, its instructions): where in ``ops`` each such run lies
        spans_of: Dict[Tuple[str, frozenset], List[Tuple[int, int]]] = {}
        runs = sorted((s, e, name) for name, s, e in runs
                      if lo <= s and e <= hi)
        ops = sorted((s, -e, name) for name, s, e in ops)
        at = 0
        for run_start, run_end, name in runs:
            entry = programs.setdefault(name, {
                "runs": 0, "run_s": [], "run_us": [], "parts": {},
                "mixed_s": 0.0, "inherited_s": 0.0, "unscoped_s": 0.0})
            entry["runs"] += 1
            entry["run_s"].append((run_end - run_start) / 1e9)
            entry["run_us"].append((run_start / 1e3, run_end / 1e3))
            own: Dict[str, float] = {}
            open_ops = []           # [end, instruction] of operations open
            while at < len(ops) and ops[at][0] < run_start:
                at += 1
            first = at
            while at < len(ops) and ops[at][0] < run_end:
                s, e, op = ops[at][0], min(-ops[at][1], run_end), ops[at][2]
                while open_ops and open_ops[-1][0] <= s:
                    open_ops.pop()
                if open_ops:        # time the operation around it loses
                    own[open_ops[-1][1]] -= e - s
                own[op] = own.get(op, 0.0) + e - s
                open_ops.append((e, op))
                at += 1
            instructions = frozenset(own)
            spans_of.setdefault((name, instructions), []).append((first, at))
            alike = own_ns.setdefault(name, {}).setdefault(instructions, {})
            for op, ns in own.items():
                alike[op] = alike.get(op, 0.0) + ns
        collectives = []
        for name, by_instructions in own_ns.items():
            registered = parts_of(name)
            entry = programs[name]
            for instructions, own in by_instructions.items():
                known = max(registered, default={}, key=lambda parts: (
                    sum(ns for op, ns in own.items() if op in parts),
                    -len(parts)))
                moving = {op for op in own if known.get(op, (None, None))
                          in ((COLLECTIVE, None), (COLLECTIVE, MIXED))}
                if moving:
                    collectives += [
                        (ops[i][2], ops[i][0] / 1e3, -ops[i][1] / 1e3)
                        for first, end in spans_of[name, instructions]
                        for i in range(first, end) if ops[i][2] in moving]
                for op, ns in own.items():
                    part, how = known.get(op, (UNSCOPED, None))
                    entry["parts"][part] = \
                        entry["parts"].get(part, 0.0) + ns / 1e9
                    if how:
                        entry[how + "_s"] += ns / 1e9
            entry["unscoped_s"] = entry["parts"].get(UNSCOPED, 0.0)
        table["collectives"][chip] = sorted(
            collectives, key=lambda event: event[1])
    return table


def part_seconds(entry: dict, part: str) -> float:
    """Seconds of ``part`` and of the parts inside it (``attention`` holds
    ``attention.cache_write``) in one program's entry of the table."""
    return sum(s for name, s in entry["parts"].items()
               if name == part or name.startswith(part + "."))
