"""Program scopes: the names the step's operations carry, and how to
read them back out of a compiled program.

Every operation a step dispatches sits under one ``jax.named_scope``
whose name begins ``roc.<class>``.  JAX writes the scope path into each
HLO instruction's ``metadata={op_name="..."}`` — and wraps it in
``jvp(...)`` / ``transpose(jvp(...))`` itself, so the direction is never
written by hand:

    jit(step)/jvp(roc.agg.op03)/while/body/...             forward
    jit(step)/transpose(jvp(roc.agg.op03))/while/body/...  backward

Under ``--remat`` every run of ops between two aggregations is a
checkpoint (``models/builder.py _computed_again``), and the backward
holds two kinds of operation under each scope of such a run: the
transposes, and the forward operations computed again to feed them.
Both are traced under ``roc.recompute``; the second kind is a direction
of its own, ``recompute``: work the backward pass does, not work of the
gradient.

    jit(step)/transpose(jvp(roc.recompute))/jvp(roc.dense.op09.add)/add         recompute
    jit(step)/transpose(jvp(transpose(jvp(roc.dense.op07.linear))))/dot_general  backward

The scopes are metadata only: the lowered program, the executable and
the compile-cache key are the same with and without them.  The device
trace names an operation by its instruction (``%fusion.28 = ...``), not
by its scope; :func:`parse_program_text` gives the instruction -> scope
map (``ObservedJit.instruction_scopes``) that joins the two.

| class | what runs under it | name |
| --- | --- | --- |
| ``agg`` | a model op that aggregates over edges (``scatter_gather``, ``fused_aggregate``, ``gat``, the dot-product ``transformer_attention``, a typed graph's ``rel_aggregate``, the softmax-weighted ``soft_aggregate``) | ``roc.agg.op<i>`` |
| ``halo`` | the feature halo exchange inside an aggregation (all-gather, ring hops) | ``roc.halo`` |
| ``dense`` | every other model op | ``roc.dense.op<i>.<kind>`` |
| ``loss`` | masked cross-entropy and the metric reductions | ``roc.loss`` |
| ``opt`` | the Adam update and the parameter casts | ``roc.opt`` |
| ``allreduce`` | the gradient / loss / metric ``psum`` across partitions | ``roc.allreduce`` |

``<i>`` is the op's index in ``Model._ops``, two digits.

A typed model (``models/rgcn.py``) adds two names, each nested inside
a scope of the table above, so the classes stay six and whatever
reads classes sees ``dense`` and ``opt`` as before: ``roc.embed``
inside ``roc.dense.op<i>.typed_input`` (assembling ``h^0`` from the
feature rows and the trainable tables; its backward slices the
cotangent into the tables' gradients), and ``roc.opt.embed`` inside
``roc.opt`` (the embedding tables' Adam update and their casts to the
compute dtype: the optimizer's stream over the tables, apart from the
weights').  Its per-relation and per-kind products are
``roc.dense.op<i>.rel_linear`` / ``.root_linear``.  The relation
aggregation's per-slot ``1 / deg`` weights are applied in register
inside the chunk scan: no separate work, no scope of their own.

A ``batch_norm`` op (``roc.dense.op<i>.batch_norm``) names the one
part of it that reduces over the vertex axis: ``roc.bn.stats``, the
float32 sums of ``x`` and ``x * x`` over the real rows (in the backward
those of ``g`` and ``g * xhat``) — and, across partitions, their
``psum``, which sits under ``roc.allreduce`` inside it: the one
collective of a step between a layer's dense ops.  A softmax-weighted
aggregation (``soft_aggregate``, ``GraphContext.soft_aggregate``) keeps
the class ``agg`` under its ``roc.agg.op<i>`` and names its elementwise
part ``roc.sagg.weights``: ``relu + eps``, the per-channel shift,
``exp``, the product ``e * m``, the division by the denominator, and in
the backward ``g / den`` and the product with ``e``; the chunk scan and
the halo stay outside it.  Neither name is a class: whatever reads
classes sees ``dense`` and ``agg`` as before.

An attention op (``gat``) splits its ``roc.agg.op<i>`` into three
phases, each a scope nested inside it (``ops/attention.py``); the
class stays ``agg``, so whatever reads classes sees one op as before,
and :func:`parse_op_phase` gives the finer rows:

| phase | what runs under it | name |
| --- | --- | --- |
| ``scores`` | ``s = a_src . z``, ``t = a_dst . z``, their per-edge gather, LeakyReLU, the padding mask | ``roc.attn.scores`` |
| ``stats`` | the softmax statistics: row max, ``exp``, denominator | ``roc.attn.stats`` |
| ``gather`` | the feature gather, the weighted sum (numerator) and the division | ``roc.attn.gather`` |

A dot-product attention op (``transformer_attention``) takes the same
three phases for its forward and both passes of its backward (the
``[k | v]`` gather and the ``K`` scores under ``scores``, the softmax
under ``stats``, the weighted sums under ``gather``), and names its
gated root path ``roc.attn.gate``: the head mean, the ``beta`` logit,
its sigmoid and ``beta r + (1 - beta) m``.  A ``layer_norm`` op
(``roc.dense.op<i>.layer_norm``) names its row moments ``roc.ln.stats``
(in the backward the two row sums of ``dx``).  Neither is a class nor
a phase: whatever reads classes or phases sees ``agg``, ``dense`` and
the three phases as before.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

PREFIX = "roc."
AGG, HALO, DENSE, LOSS, OPT, ALLREDUCE = (
    "agg", "halo", "dense", "loss", "opt", "allreduce")
CLASSES = (AGG, HALO, DENSE, LOSS, OPT, ALLREDUCE)
# the model op kinds whose scope class is ``agg``; every other kind is
# ``dense``
AGG_KINDS = ("scatter_gather", "fused_aggregate", "gat",
             "transformer_attention", "rel_aggregate", "soft_aggregate")

ATTN_PHASES = ("scores", "stats", "gather")

HALO_SCOPE = PREFIX + HALO
LOSS_SCOPE = PREFIX + LOSS
OPT_SCOPE = PREFIX + OPT
ALLREDUCE_SCOPE = PREFIX + ALLREDUCE
# a typed model's two nested names (module docstring)
EMBED_SCOPE = PREFIX + "embed"
OPT_EMBED_SCOPE = OPT_SCOPE + ".embed"
# parameters whose optimizer work runs under OPT_EMBED_SCOPE
EMBED_PARAM_PREFIX = "embed_"
# a batch_norm op's moment reductions (inside its own dense scope) and
# a softmax-weighted aggregation's elementwise part (inside its agg
# scope): names, not classes (module docstring)
BN_STATS_SCOPE = PREFIX + "bn.stats"
SAGG_WEIGHTS_SCOPE = PREFIX + "sagg.weights"
# a batch_norm op's parameters (scale, shift) and statistics (running
# mean, variance), a layer_norm op's scale and shift: kept in float32
# whatever the compute dtype
BN_PARAM_PREFIX = "bn_"
LN_PARAM_PREFIX = "ln_"
FLOAT32_PARAM_PREFIXES = (BN_PARAM_PREFIX, LN_PARAM_PREFIX)
# entered inside an attention op's own ``roc.agg.op<i>``
ATTN_SCORES_SCOPE, ATTN_STATS_SCOPE, ATTN_GATHER_SCOPE = (
    f"{PREFIX}attn.{phase}" for phase in ATTN_PHASES)
# a dot-product attention op's gated root path (inside its agg scope)
# and a layer_norm op's row moments (inside its dense scope): names,
# not phases (module docstring)
ATTN_GATE_SCOPE = PREFIX + "attn.gate"
LN_STATS_SCOPE = PREFIX + "ln.stats"

_SCOPE = re.compile(r"roc\.(" + "|".join(CLASSES) + r")(?:\.op(\d+))?")
_PHASE = re.compile(r"roc\.attn\.(" + "|".join(ATTN_PHASES) + r")")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
# "  ROOT %fusion.7 = f32[8]{0} fusion(...), ..., metadata={op_name="..."}"
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=%]+)\s+=\s")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def op_scope(index: int, kind: str) -> str:
    """The scope of ``Model._ops[index]``."""
    if kind in AGG_KINDS:
        return f"{PREFIX}{AGG}.op{index:02d}"
    return f"{PREFIX}{DENSE}.op{index:02d}.{kind}"


# entered around a run's second forward inside the backward
RECOMPUTE_SCOPE = PREFIX + "recompute"


def parse_op_name(op_name: str
                  ) -> Optional[Tuple[str, Optional[int], str]]:
    """``(class, op index or None, "fwd" | "bwd" | "recompute")`` of an
    ``op_name`` path, None when no component is a ``roc.`` scope.  The
    innermost ``roc.`` component gives the class (a ``roc.halo`` inside
    a ``roc.agg.op03`` is halo); the op index is the innermost one any
    component carries (that halo belongs to op 3); the direction is
    ``bwd`` where JAX wrapped a component in ``transpose(`` (the
    primitive of that name has no parenthesis) — except under
    ``roc.recompute``, where the step's own ``transpose(jvp(...))``
    wrapper is around everything: there one ``transpose(`` is a forward
    operation computed again inside the backward (``recompute``: only
    a step under remat has any) and a second one its transpose."""
    found = _SCOPE.findall(op_name)
    if not found:
        return None
    index = next((int(i) for _, i in reversed(found) if i), None)
    transposes = op_name.count("transpose(")
    if RECOMPUTE_SCOPE in op_name:
        # the whole backward sits in the step's one transpose(jvp(...))
        # wrapper; a run's second forward carries that one alone
        way = "recompute" if transposes <= 1 else "bwd"
    else:
        way = "bwd" if transposes else "fwd"
    return (found[-1][0], index, way)


def parse_op_phase(op_name: str
                   ) -> Optional[Tuple[str, Optional[int], str, str]]:
    """``("agg", op index, phase, "fwd" | "bwd" | "recompute")`` of an operation
    inside an attention phase (the innermost ``roc.attn.`` component),
    None for every other operation — one under ``roc.halo`` inside an
    attention op included: its class is not ``agg``."""
    key = parse_op_name(op_name)
    phases = _PHASE.findall(op_name)
    if key is None or key[0] != AGG or not phases:
        return None
    return (AGG, key[1], phases[-1], key[2])


def parse_program_text(text: str) -> Dict[str, Any]:
    """``{"module": <HloModule name>, "scopes": {<instruction name>:
    <op_name>}}`` from a compiled program's text: every instruction of
    every computation (the operations inside a ``while`` body are
    events of a device trace too), with ``""`` where the instruction
    carries no ``op_name``."""
    module = ""
    scopes: Dict[str, str] = {}
    for line in text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line, m.end())
        scopes[m.group(1)] = name.group(1) if name else ""
    return {"module": module, "scopes": scopes}


def has_scopes(text: str) -> bool:
    """Whether a program's text carries any ``roc.`` scope at all — a
    program served by a compile-cache entry written before the scopes
    existed has none (metadata is not in the cache key)."""
    return _SCOPE.search(text) is not None
