"""Engine: one compiled execution plan for one model + backend + recipe.

``compile_model(cfg, params, backend=..., recipe=...)`` is the single
entry point through which every launcher, example and benchmark selects
execution.  It resolves the backend (float / lut_float / lut / pallas),
applies the QuantRecipe PTQ when the backend calls for it, pins the
execution modes onto the config ONCE (including the Pallas
interpret-vs-Mosaic decision), and returns an ``Engine`` whose jitted
entry points all run that one plan:

    eng = runtime.compile_model(cfg, params, backend="lut")
    logits = eng.forward(mfcc)            # offline [B, F, T] -> [B, C]
    emb    = eng.embed_frames(frames)     # streaming building blocks
    logits = eng.encode_window(window)    #   (consumed by stream.engine)
    state, logits = eng.stream_step(state, chunk, fcfg)

LM families additionally expose ``init_decode_state`` / ``prefill`` /
``decode_step`` so ``launch/serve.py`` runs off the same object.

Contract (tests/test_runtime.py): for any backend, streaming logits are
bit-identical to that same engine's offline ``forward`` on the matching
audio window — the PR-2 float/LUT bit-identity guarantee restated at the
Engine level — and float/lut/pallas logits agree within the documented
PTQ + LUT-bin tolerance.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional

import jax

from repro.core import lut as lutlib
from repro.core import quant
from repro.runtime.backends import Backend, get_backend
from repro.runtime.recipe import QuantRecipe
from repro.telemetry import taps as _taps
from repro.telemetry import trace as _trace

Pytree = Any


def _model_module(cfg):
    if cfg.family == "kwt":
        from repro.models import kwt
        return kwt
    from repro.launch import steps
    return steps.model_module(cfg)


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "dtype"))


@dataclasses.dataclass
class Engine:
    """A planned model: prepared params + pinned execution config.

    ``exec_cfg`` is the ONLY config that carries softmax_mode /
    act_approx / kernel_interpret different from the user's ``cfg`` —
    drivers that build their own fused jits (e.g. the streaming server's
    joint engine+detector hop) close over ``eng.exec_cfg`` and pass
    ``eng.live_params()`` (NOT ``eng.params``: integer-resident plans
    store packed QTensors there, and dequantising inside the driver's
    own XLA module would forfeit the bit-identity contract — see
    :meth:`live_params`), so execution policy still has a single source.
    """

    cfg: Any                        # the config compile_model was given
    exec_cfg: Any                   # cfg with the backend's modes pinned
    params: Pytree                  # PTQ-applied when the backend quantizes
    backend: Backend
    recipe: Optional[QuantRecipe]
    quantized_bytes: Optional[tuple] = None   # (int bytes, float bytes)
    taps: bool = False              # forward also returns quant-health aux
    int_exec: bool = False          # integer-executing plan: the model
    #                                 consumes the packed tree directly
    #                                 (no per-call unpack stage/span)

    def __post_init__(self):
        self._mod = _model_module(self.exec_cfg)
        cfg = self.exec_cfg
        self._forward = jax.jit(lambda p, x: self._mod.forward(p, x, cfg))
        self._embed = self._encode = self._prefill = self._decode = None
        self._stream_steps = {}
        self._taps_fn = None
        self._unpack = jax.jit(quant.dequantize_tree) \
            if self.int_resident and not self.int_exec else None
        # Fast dispatch for plans whose operand tree never changes between
        # calls (no per-call unpack): pre-flatten the params ONCE and jit a
        # wrapper over the flat leaves.  Per-call argument processing then
        # walks a flat tuple of plain arrays instead of re-flattening
        # registered-dataclass QTensor nodes in Python — measured ~15 us
        # per forward on the integer-executing kwt-tiny plan, with the
        # unflatten happening only at trace time (identical jaxpr/HLO, so
        # logits are bit-identical to the tree-operand executable).
        self._forward_flat = self._flat_leaves = None
        if self._unpack is None:
            leaves, treedef = jax.tree_util.tree_flatten(self.params)
            self._flat_leaves = tuple(leaves)
            self._forward_flat = jax.jit(
                lambda lv, x: self._mod.forward(
                    jax.tree_util.tree_unflatten(treedef, lv), x, cfg))
        if cfg.family == "kwt":
            self._embed = jax.jit(
                lambda p, fr: self._mod.embed_frames(p, fr, cfg))
            self._encode = jax.jit(
                lambda p, w: self._mod.encode_window(p, w, cfg))

    def live_params(self):
        """The float operand tree the model executables run on.

        Integer-EXECUTING plans (``int_exec``) have no float view at all:
        the model executables consume the packed QTensors directly
        (``quant.int_exec_einsum``), so this returns ``params`` as-is.

        Non-executing integer-resident plans store packed int8 /
        nibble-packed int4 QTensors in ``params``; the float view is
        materialised per call by a separate jitted unpack program — the
        software analogue of the device's shift-dequantiser stage (ROM
        bytes stay packed, the float image is a transient).  Keeping the
        unpack in its OWN executable is load-bearing for the bit-identity
        contract: when quantiser ops share the model's XLA module, CPU
        fusion re-tiles unrelated reductions (LayerNorm/softmax) and
        rounding becomes weight-producer-dependent; as a separate stage
        the model executable is byte-identical to the dequantise-first
        plan and receives bit-identical operand values (po2 de-scales
        are exact).
        """
        return self.params if self._unpack is None else \
            self._unpack(self.params)

    # -- inference entry points (all jitted, params passed as operands) ----

    def forward(self, x):
        """Offline forward: kwt mfcc [B,F,T] -> logits; LM tokens -> logits.

        With ``taps`` planned (``compile_model(..., taps=True)``) returns
        ``(logits, aux)`` where ``aux`` maps tap sites to quantisation-
        health scalars (telemetry.taps).  Logits always come from the SAME
        untapped executable either way — bit-identity by construction.
        """
        tr = _trace.active_tracer()
        if tr is None and not self.taps:
            if self._forward_flat is not None:
                return self._forward_flat(self._flat_leaves, x)
            return self._forward(self.live_params(), x)
        return self._forward_instrumented(tr, x)

    def _live_traced(self, tr):
        """Operand tree under tracing.  Plans with no unpack program —
        float params, or integer-EXECUTING packed params — emit no
        ``unpack`` span: there is no unpack stage to attribute (timing
        the identity ``live_params`` walk would charge tree-flatten
        noise to a stage the plan does not have)."""
        if self._unpack is None:
            return self.params
        with tr.span("unpack"):
            return jax.block_until_ready(self.live_params())

    def _forward_instrumented(self, tr, x):
        if tr is None:                         # taps only, no tracing
            lp = self.live_params()
            return self._forward(lp, x), self._run_taps(lp, x)
        # Spans measure device work: fence each stage with
        # block_until_ready (async dispatch is preserved when untraced).
        with tr.span("forward", {"backend": self.backend.name}):
            lp = self._live_traced(tr)
            with tr.span("encode"):
                # same executable selection as the untraced path: the flat
                # pre-flattened program when the operand tree is static
                # (lp IS self.params then), so the span times the serving
                # executable rather than compiling the tree-operand twin
                logits = jax.block_until_ready(
                    self._forward_flat(self._flat_leaves, x)
                    if self._forward_flat is not None
                    else self._forward(lp, x))
            if self.taps:
                with tr.span("taps"):
                    aux = jax.block_until_ready(self._run_taps(lp, x))
                return logits, aux
        return logits

    def _run_taps(self, lp, x):
        """The separate jitted aux program of a ``taps=True`` plan.

        Re-traces ``forward`` with the telemetry.taps collector active
        and returns ONLY the health statistics; served logits never come
        from this executable.  Keeping the tapped trace out of the
        serving program is load-bearing for the bit-identity criterion:
        extra aux outputs change what CPU XLA fuses, which re-tiles
        reductions and shifts logit rounding (same mechanism the
        separate unpack stage guards against — see ``live_params``).
        The cost — a second forward pass — is a diagnostic-mode cost.
        """
        if self._taps_fn is None:
            mod, cfg = self._mod, self.exec_cfg

            def aux_program(p, x):
                with _taps.collecting() as col:
                    logits = mod.forward(p, x, cfg)
                    _taps.tap_activation("logits", logits, cfg)
                return _taps.pack(col)

            self._taps_fn = jax.jit(aux_program)
        return self._taps_fn(lp, x)

    def embed_frames(self, frames):
        """[B, t, F] time-major frames -> [B, t, d] patch embeddings."""
        self._require_kwt("embed_frames")
        return self._embed(self.live_params(), frames)

    def encode_window(self, window):
        """Assembled [B, T, d] window -> logits [B, n_classes]."""
        self._require_kwt("encode_window")
        return self._encode(self.live_params(), window)

    def stream_step(self, state, chunk, fcfg):
        """One hop of incremental inference (stream.engine.stream_step under
        this engine's plan): (state, chunk [B, k*hop]) -> (state, logits)."""
        self._require_kwt("stream_step")
        step = self._stream_steps.get(fcfg)
        if step is None:
            from repro.stream import engine as stream_engine
            cfg = self.exec_cfg
            step = jax.jit(lambda p, s, c: stream_engine.stream_step(
                p, s, c, cfg, fcfg))
            self._stream_steps[fcfg] = step
        tr = _trace.active_tracer()
        if tr is None:
            return step(self.live_params(), state, chunk)
        with tr.span("stream_step", {"backend": self.backend.name}):
            lp = self._live_traced(tr)
            with tr.span("step"):
                return jax.block_until_ready(step(lp, state, chunk))

    # -- LM serving entry points ------------------------------------------

    def init_decode_state(self, batch: int, max_len: int):
        return self._mod.init_decode_state(self.exec_cfg, batch, max_len)

    def prefill(self, tokens, state):
        if self._prefill is None:
            cfg = self.exec_cfg
            self._prefill = jax.jit(
                lambda p, t, s: self._mod.prefill(p, t, cfg, s))
        tr = _trace.active_tracer()
        if tr is None:
            return self._prefill(self.live_params(), tokens, state)
        with tr.span("prefill", {"backend": self.backend.name}):
            lp = self._live_traced(tr)
            with tr.span("encode"):
                return jax.block_until_ready(self._prefill(lp, tokens, state))

    def decode_step(self, token, state):
        if self._decode is None:
            cfg = self.exec_cfg
            self._decode = jax.jit(
                lambda p, t, s: self._mod.decode_step(p, t, cfg, s))
        tr = _trace.active_tracer()
        if tr is None:
            return self._decode(self.live_params(), token, state)
        with tr.span("decode_step", {"backend": self.backend.name}):
            lp = self._live_traced(tr)
            with tr.span("encode"):
                return jax.block_until_ready(self._decode(lp, token, state))

    # -- introspection -----------------------------------------------------

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @property
    def interpret(self) -> Optional[bool]:
        """The plan-time Pallas decision (None: plan uses no kernels)."""
        uses = self.backend.uses_kernels or \
            self.exec_cfg.attn_impl == "flash_lut"
        return self.exec_cfg.kernel_interpret if uses else None

    @property
    def rom_bytes(self) -> int:
        """TRUE packed bytes of the integer weight image the plan deploys
        (nibble-packed below 5 bits; 0 when nothing is quantised).

        KWT-Tiny at the paper recipe: 1512 B of int8 weight ROM — the
        paper's 1.65 kB figure counts its 146 rank-1 params (biases,
        norm scales) at int8 too, which we keep float per §IV.  A 4-bit
        recipe halves this (±nibble padding).
        """
        return self.quantized_bytes[0] if self.quantized_bytes else 0

    @property
    def lut_bytes(self) -> int:
        """LUT ROM footprint of the plan (paper: 2.69 kB; 0 for float)."""
        return lutlib.make_lut_bank().rom_bytes if self.backend.uses_lut else 0

    @property
    def param_bytes(self) -> int:
        """Deployed parameter bytes: packed ints + residual floats when
        quantised, plain float tree bytes otherwise."""
        if self.quantized_bytes is not None:
            return sum(self.quantized_bytes)
        return _tree_bytes(self.params)

    @property
    def int_resident(self) -> bool:
        """True when the Engine's live tree holds stored-integer QTensors
        (the lut/pallas weight path) rather than a dequantised float copy."""
        return _has_qtensors(self.params)

    def describe(self, analyze: bool = False, cost: bool = False) -> str:
        """One-line plan summary.  ``analyze=True`` appends the static-
        analysis verdict (repro.analysis), running the pass pipeline on
        first use; a verdict cached by an earlier ``check_engine`` call
        is appended either way.  ``cost=True`` appends the static cost
        model's totals (repro.perf) plus the paper-style per-(stage, op)
        table priced on the RV32 MCU model — the one-stop answer to
        "what does this plan cost and where"."""
        if analyze and not hasattr(self, "_analysis_verdict"):
            from repro import analysis
            analysis.check_engine(self)
        q = "" if self.recipe is None else \
            f", w=2^{self.recipe.weight_exponent}" \
            f"/x=2^{self.recipe.input_exponent} " \
            f"int{self.recipe.bits} {self.recipe.rounding}" + \
            (" int-exec" if self.int_exec else
             " resident" if self.int_resident else "")
        interp = "" if self.interpret is None else \
            f", pallas={'interpret' if self.interpret else 'mosaic'}"
        attn = "" if self.exec_cfg.attn_impl == "xla" else \
            f", attn={self.exec_cfg.attn_impl}"
        verdict = getattr(self, "_analysis_verdict", None)
        verdict = f" | {verdict}" if verdict else ""
        line = (f"Engine[{self.backend.name}] {self.exec_cfg.name}: "
                f"params {self.param_bytes} B, rom {self.rom_bytes} B, "
                f"lut {self.lut_bytes} B{q}{interp}{attn}{verdict}")
        if cost:
            from repro import perf
            rep = perf.engine_cost(self, batch=1)
            mcu = perf.PAPER_MCU
            line += (f" | cost/fwd: {rep.flops:.0f} flops, "
                     f"{rep.bytes:.0f} B moved, AI {rep.intensity:.2f}, "
                     f"~{mcu.cycles(rep.flops, rep.bytes):.3g} "
                     f"{mcu.name} cycles\n" + rep.table(mcu))
        return line

    def _require_kwt(self, what: str):
        if self.exec_cfg.family != "kwt":
            raise NotImplementedError(
                f"{what} is a KWT streaming entry point; family="
                f"{self.exec_cfg.family!r} engines expose forward/prefill/"
                "decode_step")


class EngineHandle:
    """A swap-safe reference to the live Engine of a serving cell.

    Serving loops read ``handle.engine`` (or call the delegating entry
    points) each hop; ``cell.hotswap`` replaces the Engine atomically
    under the handle's lock after warming + probe-parity verification.
    Lane state (stream rings, detector state, decode caches) lives
    outside the Engine, so a swap changes only params + executables —
    in-flight lanes keep their positions and no hop is dropped.

    ``swap`` enforces plan compatibility by default: the incoming
    Engine must share the exec config (same arch dims + pinned modes)
    and a param tree of identical structure/shapes, so the serving
    loop's jitted programs keep their compiled executables and the swap
    costs one reference assignment, not a recompile mid-traffic.
    """

    def __init__(self, engine: Engine):
        self._lock = threading.Lock()
        self._engine = engine
        self._generation = 0
        self._live_cache = None          # (generation, unpacked float view)

    @property
    def engine(self) -> Engine:
        return self._engine

    @property
    def generation(self) -> int:
        """Bumps once per completed swap (serving loops key caches on it)."""
        return self._generation

    def live_params(self):
        """The current Engine's float operand tree, cached per generation
        (one unpack per swap instead of one per hop for integer-resident
        plans; see :meth:`Engine.live_params`)."""
        with self._lock:
            gen, eng = self._generation, self._engine
        cache = self._live_cache
        if cache is not None and cache[0] == gen:
            return cache[1]
        live = eng.live_params()
        self._live_cache = (gen, live)
        return live

    def swap(self, new_engine: Engine, *, strict: bool = True) -> Engine:
        """Install ``new_engine``; returns the Engine it replaced."""
        if strict:
            old = self._engine
            if new_engine.exec_cfg != old.exec_cfg:
                raise ValueError(
                    "hot-swap across exec configs would recompile the "
                    f"serving programs mid-traffic: {old.exec_cfg.name}/"
                    f"{old.backend.name} -> {new_engine.exec_cfg.name}/"
                    f"{new_engine.backend.name} (swap(strict=False) to "
                    "force)")
            old_shapes = [(getattr(x, "shape", None))
                          for x in jax.tree.leaves(old.params)]
            new_shapes = [(getattr(x, "shape", None))
                          for x in jax.tree.leaves(new_engine.params)]
            if old_shapes != new_shapes:
                raise ValueError("hot-swap param tree shape mismatch")
        with self._lock:
            old, self._engine = self._engine, new_engine
            self._generation += 1
            self._live_cache = None
        return old


def _has_qtensors(tree) -> bool:
    return any(isinstance(leaf, quant.QTensor) for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, quant.QTensor)))


def _recipe_from_tree(cfg, tree) -> QuantRecipe:
    """Reconstruct the deployment recipe of an already-quantised tree from
    its own QTensor metadata (bits / exponent / per-channel), so
    ``Engine.recipe`` and ``describe()`` report the artifact's actual
    policy rather than the config default.  Rounding is storage-
    irrelevant post-quantisation and keeps the config default."""
    qleaves = [leaf for leaf in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, quant.QTensor))
        if isinstance(leaf, quant.QTensor)]
    return QuantRecipe.from_config(
        cfg, bits=qleaves[0].bits,
        weight_exponent=min(q.exponent for q in qleaves),
        per_channel=any(q.axis_exponents is not None for q in qleaves))


def _lm_partial_resident(qtree: dict) -> dict:
    """LM partial residency: keep the big vocab-facing leaves (embedding
    table / untied head) packed for integer execution, dequantise the
    per-block stack.  ``lax.scan`` carries the blocks as stacked leaves
    and per-channel QTensor metadata (``axis_exponents`` over the last
    axis) has no leading layer axis to scan over, so block weights take
    the dequantise-first path; the embedding is consumed row-wise via
    ``quant.gather_descale`` (descale only the looked-up rows)."""
    packed = {k: v for k, v in qtree.items() if k in ("embed", "lm_head")}
    rest = {k: v for k, v in qtree.items() if k not in packed}
    return {**quant.dequantize_tree(rest), **packed}


def _pin_int_exec(exec_cfg, recipe: QuantRecipe):
    """Pin the integer-execution plan flavour onto the exec config: the
    activation quantiser shares the recipe's eq-9 semantics (input
    exponent, residual width), so layers and the artifact agree on the
    fixed-point grid by construction."""
    from repro.configs.base import QuantConfig
    qc = exec_cfg.quant if exec_cfg.quant is not None else QuantConfig()
    qc = dataclasses.replace(qc, input_exponent=recipe.input_exponent,
                             residual_bits=recipe.residual_bits)
    return exec_cfg.with_(int_exec=True, quant=qc)


def compile_model(cfg, params, backend="float",
                  recipe: QuantRecipe | None = None,
                  interpret: bool | None = None,
                  attention: str | None = None,
                  integer_resident: bool | None = None,
                  integer_exec: bool | None = None,
                  taps: bool = False) -> Engine:
    """Plan execution of ``params`` under ``backend``.

    ``recipe=None`` -> the backend's default policy: quantising backends
    (lut_float / lut / pallas) derive a QuantRecipe from ``cfg.quant``;
    the float backend leaves params untouched.  Passing an explicit
    recipe forces PTQ on any backend (e.g. float ops on quantised weights
    — Table IX's middle column).  ``params`` may also be an
    already-quantised QTensor tree (a packed QAT export artifact): it is
    deployed as-is, no float detour and no re-quantisation.

    ``integer_resident`` overrides the backend's weight-residency policy
    (default: ``lut``/``pallas`` keep the stored int8 / nibble-packed
    int4 QTensors live inside the jitted program and de-scale in the
    matmul epilogue — packed weight bytes; other backends deploy the
    dequantised float copy).  Integer residency currently covers the
    ``kwt`` family (the paper model whose layers consume QTensors);
    LM-scale families get PARTIAL residency under integer execution
    (embedding/head stay packed, scanned blocks dequantise — see
    ``_lm_partial_resident``) and otherwise fall back to
    dequantise-first.

    ``integer_exec`` overrides the backend's execution policy (default:
    ``lut``/``pallas`` integer-EXECUTE resident plans — linear layers
    quantise activations to the recipe's eq-9 grid and multiply the
    stored int payload directly, per-channel po2 requant in the
    epilogue, no per-call unpack stage).  ``integer_exec=False`` keeps
    the PR-5 dequantise-per-call resident plan, whose logits are
    bit-identical to dequantise-first; integer execution instead matches
    the Q8.24 fixed-point reference (activation rounding + INT16
    residual clip are part of the plan's math, as on the device).

    ``interpret`` overrides the plan-time Pallas interpret/Mosaic
    auto-decision (tests only).  ``attention`` overrides the backend's
    attention realisation: ``"flash_lut"`` routes cacheless attention
    through the flash-LUT Pallas kernel (``kernels.lut_attention`` —
    online softmax with the eq-11 ROM), ``"xla"`` keeps the chunked sdpa
    path.

    ``taps=True`` plans the quantisation-health aux: ``forward`` returns
    ``(logits, aux)`` where aux carries per-layer int8 saturation, LUT
    out-of-domain fractions and Q8.24 headroom (telemetry.taps).  Logits
    are served by the same untapped executable as a ``taps=False`` plan
    (bit-identical); taps off costs nothing (the flag is a plain Python
    branch, no recompile).
    """
    be = get_backend(backend)
    pre_quantized = _has_qtensors(params)
    if recipe is None and pre_quantized:
        recipe = _recipe_from_tree(cfg, params)
    elif recipe is None and be.quantize:
        recipe = QuantRecipe.from_config(cfg)
    qbytes = None
    int_exec = False
    exec_flag = be.int_exec if integer_exec is None else bool(integer_exec)
    if recipe is not None or pre_quantized:
        qtree = params if pre_quantized else recipe.quantize(params)
        # ROM footprint is the artifact's full packed image, independent
        # of which leaves the plan keeps resident.
        qbytes = quant.tree_quantized_bytes(qtree)
        if integer_resident is not None or cfg.family == "kwt":
            resident = (be.int_resident and cfg.family == "kwt"
                        if integer_resident is None
                        else bool(integer_resident))
            params = qtree if resident else quant.dequantize_tree(qtree)
            int_exec = exec_flag and resident
        elif exec_flag and be.int_resident and isinstance(qtree, dict) \
                and "embed" in qtree:
            params = _lm_partial_resident(qtree)
            int_exec = True
        else:
            params = quant.dequantize_tree(qtree)
    exec_cfg = be.configure(cfg, interpret=interpret, attention=attention)
    if int_exec:
        exec_cfg = _pin_int_exec(exec_cfg, recipe)
    return Engine(cfg=cfg, exec_cfg=exec_cfg, params=params, backend=be,
                  recipe=recipe, quantized_bytes=qbytes, taps=taps,
                  int_exec=int_exec)
