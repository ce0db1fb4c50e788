"""Jittable streaming stages: the TPU compute plane's unit of composition.

This is where the reference's per-block accelerator dispatch (Vulkan/WGPU compute shaders,
``blocks/vulkan.rs:96+``) is re-designed TPU-first: instead of one device dispatch per block,
adjacent DSP blocks compose into ONE jitted XLA program (`SURVEY §7.5`). A :class:`Stage` is a
pure function ``(carry, frame) -> (carry, out)`` with static frame shape — streaming state
(filter history, oscillator phase) is explicit carry, which keeps the program jit-compatible
and lets frame t+1's dispatch chain on frame t's carry entirely on-device (no host sync
between frames).

Rate changes are rational and static (``in_per_out``/``out_per_in``), mirroring the
``ComputationStatus`` frame contract of ``futuredsp/lib.rs:33-45``.
"""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mxu_fft

__all__ = ["Stage", "Pipeline", "FanoutPipeline", "MergeStage", "DagPipeline",
           "apply_merge_stage", "add_merge_stage", "interleave_merge_stage",
           "concat_merge_stage", "fir_stage", "fft_stage",
           "mag2_stage", "log10_stage", "lora_downchirp", "lora_dechirp_dft",
           "rotator_stage", "quad_demod_stage", "apply_stage", "fftshift_stage",
           "decimate_stage", "moving_avg_stage"]


def _donate_argnums(donate) -> tuple:
    """Normalize a donation spec into jit ``donate_argnums``.

    ``True`` donates the carries (argnum 0, the historical default), ``False``
    donates nothing, and a sequence is an explicit per-argnum mask — the knob
    multi-output fan-out programs need: the carries and the input wire parts
    are donation-safe (each dispatch consumes them), but a value that is
    multiply-consumed ACROSS outputs (the fan-out producer boundary) must
    never be threaded through as a donated argument — it rides the carry as a
    program output root instead (see :class:`FanoutPipeline`)."""
    if donate is True:
        return (0,)
    if not donate:
        return ()
    return tuple(int(i) for i in donate)


@dataclass
class Stage:
    """One streaming stage.

    ``fn(carry, x) -> (carry, y)`` must be jax-traceable with static shapes: for an input
    frame of n items it returns ``n * ratio`` items (ratio = out/in, a Fraction).
    """

    fn: Callable[[Any, jnp.ndarray], Tuple[Any, jnp.ndarray]]
    init_carry: Callable[[np.dtype], Any]
    ratio: Fraction = Fraction(1, 1)
    out_dtype: Optional[np.dtype] = None          # None = same as input
    frame_multiple: int = 1                       # input frame must divide this
    name: str = "stage"
    lti: Optional[Tuple[np.ndarray, int, int, str]] = None  # (taps, decim, fft_len, impl)
    #   when the stage is a linear time-invariant FIR — lets Pipeline merge adjacent
    #   FIRs into one (impl: the builder used for the merged stage, see _merge_lti)
    update: Optional[Callable[..., Any]] = None   # host-side ``(carry, **params) -> carry``
    #   runtime control hook: parameters (taps, phase_inc, …) live in the carry, so a
    #   retune is carry surgery between dispatches — NO recompile, frames stay in flight
    lower: Optional[Callable[[str], Optional["Stage"]]] = None
    #   interior-precision hook (ops/precision.py): return this stage rebuilt with its
    #   accumulation/taps lowered to the given precision ("bf16"; "int8" where the
    #   stage declares support), or None when unsupported — the SNR-budgeted lowering
    #   pass only considers stages that offer the hook; everything else gets at most
    #   an interior-EDGE cast
    compute_dtype: str = "f32"                    # dominant accumulation dtype of the
    #   traced program ("f32" | "bf16" | "int8") — keys the MFU denominator on the
    #   right per-dtype chip peak (utils/roofline.detect_peaks)
    route: Optional[Tuple[Optional[str], Optional[str], Optional[str]]] = None
    #   (impl, fft_impl, precision) — the builder's per-call-site selection for
    #   kernel-backed stages (fir/fft/channelizer); the decimating FIRs' matvec
    #   route names the row width that compiled in the impl slot ("rows128",
    #   _row_width). LTI merging preserves pins
    #   only when both sides agree (a pin must never be silently dropped), the
    #   cost-cache marker includes it (two same-shape stages on different
    #   routes compile different-cost programs), and
    #   ops/precision.pallas_stage_count resolves pallas routing from it

    counters: Optional[Callable[[np.ndarray], dict]] = None
    #   on a pipeline's LAST stage: a few integers read from one landed output
    #   frame (a record header), which TpuKernel adds to the ``emit`` span's
    #   args while the span recorder is on; never called when it is off

    def __repr__(self):
        return f"Stage({self.name}, ratio={self.ratio})"


@dataclass
class MergeStage:
    """A fan-IN stage: K ordered inputs joined into one output stream.

    ``fn(carry, xs) -> (carry, y)`` with ``xs`` a K-tuple of arrays, jax-
    traceable with static shapes — the merge node of a device-plane DAG
    (:class:`DagPipeline`): the WLAN ``{demod, chan-est} → decode`` join and
    the FM ``{audio, RDS} → mux`` both land here. The rate contract is per
    MODE:

    * ``mode="equal"`` — every input arrives at the SAME path rate (the
      :class:`DagPipeline` constructor enforces it; a violating region is a
      rate-contract error the devchain finder declines on). For n items per
      input the output is ``n * ratio`` items (``apply_merge_stage``: ratio 1;
      ``interleave_merge_stage(k)``: ratio k).
    * ``mode="concat"`` — inputs may arrive at DIFFERENT rates; the output is
      ``sum(n_i) * ratio`` items (``concat_merge_stage``: the mux join).

    Stream tags crossing a merge ride the PRIMARY input (index 0): on the
    actor path (``tpu/frames.TpuMergeStage``) only input 0's tags propagate
    (rebased by ``ratio`` — concat places input 0 at offset 0, so the same
    index math holds), and the fused path rebases region-input tags through
    each sink's primary-chain ``tag_ratio`` — the two stay bit-identical.
    """

    fn: Callable[[Any, Tuple[jnp.ndarray, ...]], Tuple[Any, jnp.ndarray]]
    init_carry: Callable[[np.dtype], Any]
    k: int
    mode: str = "equal"                           # "equal" | "concat"
    ratio: Fraction = Fraction(1, 1)
    out_dtype: Optional[np.dtype] = None          # None = same as input
    frame_multiple: int = 1                       # per-INPUT requirement
    name: str = "merge"
    update: Optional[Callable[..., Any]] = None

    def __post_init__(self):
        assert self.mode in ("equal", "concat"), self.mode
        assert self.k >= 2, "a merge needs >= 2 inputs"

    def __repr__(self):
        return f"MergeStage({self.name}, k={self.k}, mode={self.mode})"


def apply_merge_stage(f: Callable[..., jnp.ndarray], k: int,
                      out_dtype=None, name: str = "merge") -> MergeStage:
    """Elementwise K-way join: ``y = f(x_0, …, x_{K-1})`` over equal-length
    inputs (``mode="equal"``, ratio 1) — the device-plane ``Combine``
    (``blocks/functional.py``) generalized to K inputs."""

    def fn(carry, xs):
        return carry, f(*xs)

    return MergeStage(fn, lambda d: jnp.zeros(()), k, "equal",
                      Fraction(1, 1), out_dtype, 1, name)


def add_merge_stage(k: int, name: str = "add_merge") -> MergeStage:
    """Elementwise sum of K equal-rate inputs (diversity/branch combining)."""

    def fn(carry, xs):
        y = xs[0]
        for x in xs[1:]:
            y = y + x
        return carry, y

    return MergeStage(fn, lambda d: jnp.zeros(()), k, "equal",
                      Fraction(1, 1), None, 1, name)


def interleave_merge_stage(k: int, name: str = "interleave") -> MergeStage:
    """Item-interleave K equal-rate inputs: ``y[i·K + j] = x_j[i]`` (K· the
    per-input rate) — the symbol-mux join."""

    def fn(carry, xs):
        return carry, jnp.stack(xs, axis=1).reshape(-1)

    return MergeStage(fn, lambda d: jnp.zeros(()), k, "equal",
                      Fraction(k, 1), None, 1, name)


def concat_merge_stage(k: int, name: str = "concat_merge") -> MergeStage:
    """Frame-concatenate K inputs (rates may differ): ``y = x_0 ++ … ++
    x_{K-1}`` per frame — the FM ``{audio, RDS} → mux`` style join where each
    branch contributes its own item count."""

    def fn(carry, xs):
        return carry, jnp.concatenate(xs)

    return MergeStage(fn, lambda d: jnp.zeros(()), k, "concat",
                      Fraction(1, 1), None, 1, name)


class Pipeline:
    """A fused chain of stages compiled as a single XLA program.

    The composition is where TPU wins over per-block GPU dispatch: XLA fuses the
    elementwise stages into the FIR/FFT hot ops, so a NullSource→FIR→FFT→|x|² chain is one
    kernel launch per frame instead of four buffer hops.
    """

    def __init__(self, stages: Sequence[Stage], in_dtype, optimize: bool = True):
        self.in_dtype = np.dtype(in_dtype)
        self.stages = (_merge_lti(list(stages), self.in_dtype)
                       if optimize else list(stages))
        dtype = self.in_dtype
        fm = 1                      # required input-frame multiple
        r = Fraction(1, 1)          # cumulative rate in front of each stage
        for s in self.stages:
            # stage input = frame_in * r must be integral and a multiple of s.frame_multiple:
            # frame_in must be a multiple of reduce(m_i / r).numerator (see Fraction math)
            need = Fraction(s.frame_multiple, 1) / r
            fm = int(np.lcm(fm, need.numerator))
            r *= s.ratio
            fm = int(np.lcm(fm, r.denominator))   # integral intermediate frame sizes
            if s.out_dtype is not None:
                dtype = np.dtype(s.out_dtype)
        self.frame_multiple = fm
        self.ratio = r
        self.out_dtype = dtype
        self._fn = None
        self._wired_fns = {}        # (wire name, k) -> wrapped fn (stable for jit cache)

    def init_carry(self):
        dtype = self.in_dtype
        carries = []
        for s in self.stages:
            carries.append(s.init_carry(dtype))
            if s.out_dtype is not None:
                dtype = np.dtype(s.out_dtype)
        return tuple(carries)

    def fn(self):
        if self._fn is None:
            stages = self.stages

            def run(carries, x):
                new_c = []
                for s, c in zip(stages, carries):
                    # metadata only: the stage's name on every op it lowers
                    # to, for the device trace (docs/observability.md)
                    with jax.named_scope(s.name):
                        c, x = s.fn(c, x)
                    new_c.append(c)
                return tuple(new_c), x

            self._fn = run
        return self._fn

    def compile(self, frame_size: int, device=None, donate=True):
        """Jit for a fixed frame size; returns (compiled_fn, initial device carry).

        Placement follows the data: put the carry (and inputs) on ``device``; jit then
        dispatches there without a deprecated device= argument.

        ``donate``: ``True`` donates the carries (argnum 0), ``False`` nothing,
        or an explicit argnum sequence (per-argnum donation mask — see
        :func:`_donate_argnums`).
        """
        assert frame_size % self.frame_multiple == 0, \
            f"frame_size {frame_size} not a multiple of {self.frame_multiple}"
        fn = jax.jit(self.fn(), donate_argnums=_donate_argnums(donate))
        carry = self.init_carry()
        if device is not None:
            carry = jax.device_put(carry, device)
        return fn, carry

    def wired_fn(self, wire, k: int = 1, words: bool = False):
        """The stage chain with the wire codec's decode PROLOG and encode EPILOG
        fused in: ``(carries, *in_parts) -> (carries, out_parts)``. Dequantized
        frames exist only inside the XLA program — they never round-trip
        through HBM as a separate dispatch (``ops/wire.py``). ``words``: the
        payload part arrives as the 32-bit words it crossed the link in and
        the prolog is the wire's ``decode_words_jax`` (only
        :meth:`packed_wired_fn` asks for it, for a slot the wire's
        ``pair_words`` names).

        ``k > 1`` returns the MEGABATCH form: each wire part gains a leading
        ``[k]`` axis and a ``lax.scan`` runs the k frames through the chain in
        ONE program call with the carry chained frame-to-frame — per-call host
        dispatch overhead is amortized k× (the ``frames_per_dispatch`` knob of
        ``TpuKernel``/``tpu/autotune.py``). Output parts carry the same leading
        axis. Functions are cached per ``(wire, k)`` so the jit identity stays
        stable across compiles."""
        from .wire import get_wire
        wire = get_wire(wire)
        key = (wire.name, int(k)) + (("words",) if words else ())
        if key not in self._wired_fns:
            inner = self.fn()
            in_dt, w = self.in_dtype, wire
            decode = w.decode_words_jax if words else w.decode_jax

            def run(carries, *parts):
                with jax.named_scope("wire_decode"):
                    x = decode(parts, in_dt)
                carries, y = inner(carries, x)
                with jax.named_scope("wire_encode"):
                    return carries, w.encode_jax(y)

            if k == 1:
                self._wired_fns[key] = run
            else:
                def run_scan(carries, *parts):
                    def body(c, p):
                        return run(c, *p)
                    return jax.lax.scan(body, carries, tuple(parts))

                self._wired_fns[key] = run_scan
        return self._wired_fns[key]

    def packed_wired_fn(self, wire, k: int = 1, packed=None):
        """:meth:`wired_fn` with the COALESCED-uplink slicing prolog fused in
        front: ``(carries, packed_u32) -> (carries, out_parts)``. ``packed`` is
        an ``ops/xfer.PackedLayout`` — the offset table both the host packer
        and this unpacker derive from the wire codec, so they cannot
        disagree. The buffer enters as 32-bit words; ``unpack`` is one slice
        per slot, and a slot the wire's ``pair_words`` names (sc16 under a
        complex ``in_dtype``: a complex sample a word) stays words for
        ``wire_decode`` to split by two shifts, so no array with a minor
        dimension below the lane count stands between the program's input
        and the first stage's (:meth:`pair_word_slots` names them). The
        host pays ONE ``device_put`` per dispatch group instead of
        ``len(parts)``. Cached per ``(wire, k, layout)`` so the jit identity
        stays stable across compiles, exactly like :meth:`wired_fn`."""
        from .wire import get_wire
        wire = get_wire(wire)
        key = (wire.name, int(k), "packed", packed.key)
        if key not in self._wired_fns:
            lay = packed
            as_words = self.pair_word_slots(wire, lay)
            inner = self.wired_fn(wire, k, words=any(as_words))

            def run_packed(carries, buf):
                with jax.named_scope("unpack"):
                    parts = lay.unpack_jax(buf, as_words)
                return inner(carries, *parts)

            self._wired_fns[key] = run_packed
        return self._wired_fns[key]

    def pair_word_slots(self, wire, packed) -> tuple:
        """One flag per slot of ``packed``: does the prolog of
        :meth:`packed_wired_fn` decode it a word a sample? Read from the
        slot's dtype and shape and ``in_dtype`` (``Wire.pair_words``); empty
        without a layout. Their sum is ``TpuKernel``'s
        ``uplink_word_slots``."""
        from .wire import get_wire
        if packed is None:
            return ()
        w = get_wire(wire)
        return tuple(w.pair_words(sh, dt, self.in_dtype)
                     for sh, dt, _off, _nb in packed.slots)

    def compile_wired(self, frame_size: int, wire, device=None,
                      donate=True, k: int = 1, packed=None):
        """:meth:`compile` for the wired form: the compiled fn consumes/produces
        wire parts (see :meth:`wired_fn`); returns (compiled_fn, initial carry).
        ``k > 1`` compiles the megabatch scan form (parts carry a leading
        ``[k]`` frame axis). ``donate`` accepts the same bool-or-argnums
        per-argnum mask as :meth:`compile`. ``packed`` (an
        ``ops/xfer.PackedLayout``) compiles the single-buffer coalesced form
        instead — the fn consumes ONE packed uint32 array
        (:meth:`packed_wired_fn`); only the carries (argnum 0) can donate
        there, so an explicit parts-argnum mask is clamped."""
        assert frame_size % self.frame_multiple == 0, \
            f"frame_size {frame_size} not a multiple of {self.frame_multiple}"
        if packed is not None:
            donate = bool(donate) if not isinstance(donate, (tuple, list)) \
                else (0 in tuple(donate))
            fn = jax.jit(self.packed_wired_fn(wire, k, packed),
                         donate_argnums=_donate_argnums(donate))
        else:
            fn = jax.jit(self.wired_fn(wire, k),
                         donate_argnums=_donate_argnums(donate))
        carry = self.init_carry()
        if device is not None:
            carry = jax.device_put(carry, device)
        return fn, carry

    def out_items(self, in_items: int) -> int:
        q = Fraction(in_items) * self.ratio
        assert q.denominator == 1
        return int(q)

    # -- carry checkpointing (the device-plane recovery contract) -------------
    # A pipeline's streaming state is EXPLICIT carry (module docstring), which
    # makes the whole program a pure function of (carry, frame): snapshotting
    # the carry at frame N and replaying frames N+1… from their host staging
    # copies reproduces an unfailed run bit-for-bit. These helpers give the
    # kernel blocks (tpu/kernel_block.py) a pipeline-owned flatten/validate/
    # restore surface so checkpoint integrity is checked against the carry
    # CONTRACT (tree structure + per-leaf shape/dtype), not ad hoc.

    def snapshot_carry(self, carry):
        """Flatten a live carry into ``(host_fetches, treedef)``: one zero-arg
        thunk per leaf that yields the host value. Device leaves begin their
        D2H NOW (``ops/xfer.start_host_transfer`` — the snapshot rides the
        existing D2H lane, off the dispatch critical path); host leaves pass
        through. The caller must materialize the thunks before the next
        dispatch donates the carry buffers (donation fence — a donated buffer
        read after reuse raises, never silently corrupts)."""
        import jax

        from .xfer import start_host_transfer
        leaves, treedef = jax.tree_util.tree_flatten(carry)
        fins = [start_host_transfer(leaf, _instrument=False)
                if isinstance(leaf, jax.Array) else (lambda v=leaf: v)
                for leaf in leaves]
        return fins, treedef

    def carry_matches(self, leaves, treedef, template) -> bool:
        """Integrity check of a materialized snapshot against a live carry
        ``template`` (same pipeline, same compile): tree structure and every
        leaf's shape/dtype must agree — the restore-path validation that lets
        a corrupted checkpoint candidate (the ``carry`` fault site) be
        rejected in favor of the previous one."""
        import jax
        t_leaves, t_def = jax.tree_util.tree_flatten(template)
        if treedef != t_def or len(leaves) != len(t_leaves):
            return False
        for leaf, t in zip(leaves, t_leaves):
            a = np.asarray(leaf)
            if a.shape != tuple(np.shape(t)) or \
                    a.dtype != np.dtype(getattr(t, "dtype", a.dtype)):
                return False
        return True

    def restore_carry(self, leaves, treedef, device=None):
        """Rebuild a device carry from a materialized host snapshot (complex
        leaves ride the pair shim — ``ops/xfer.to_device``)."""
        import jax

        from .xfer import to_device
        return jax.tree_util.tree_unflatten(
            treedef, [to_device(np.asarray(l), device) for l in leaves])

    def update_stage(self, carries, stage, _validate_only: bool = False, **params):
        """Runtime control: apply a stage's ``update`` hook to its slot in ``carries``.

        ``stage``: post-merge index or stage ``name`` (LTI merging may have renamed a
        FIR to ``"a*b"`` — address the pipeline you built, check ``.stages``). Returns
        the new carries tuple; the in-flight frames that captured the old carry are
        untouched, every later dispatch sees the new parameters — the device-path
        retune-while-running of ``examples/fm-receiver/src/main.rs:83-155``.

        ``_validate_only``: resolve the stage and check it has an update hook
        WITHOUT touching carries (which may be None) — for callers that must
        queue an update before any carry exists (TpuStage's lazy compile) but
        still want to reject a bad stage name immediately.
        """
        if isinstance(stage, str):
            hits = [i for i, s in enumerate(self.stages) if s.name == stage]
            if not hits:
                raise KeyError(
                    f"no stage named {stage!r} in {[s.name for s in self.stages]}")
            if len(hits) > 1:
                raise KeyError(f"stage name {stage!r} is ambiguous (indices {hits})")
            idx = hits[0]
        else:
            idx = int(stage)
            if not 0 <= idx < len(self.stages):
                raise KeyError(f"stage index {idx} out of range "
                               f"({len(self.stages)} stages)")
        s = self.stages[idx]
        if s.update is None:
            raise ValueError(f"stage {s.name!r} has no runtime-update hook")
        if _validate_only:
            return carries
        carries = list(carries)
        carries[idx] = s.update(carries[idx], **params)
        return tuple(carries)


class FanoutPipeline:
    """A fan-out stage DAG compiled as ONE multi-output XLA program.

    Shape: ``producer stages → boundary → N branch stage chains``. The
    producer computes once per frame; its boundary value feeds every branch
    INSIDE the program (no host round trip, no duplicate H2D — the
    whole-program fusion argument of arXiv:1810.09868 applied across a
    broadcast), and the program returns one output frame per branch. This is
    the compute plane of the device-graph fan-out fusion pass
    (``runtime/devchain.py``): a ``sync → {demod, channel-est}`` or
    ``FM → {audio, RDS}`` flowgraph region becomes one dispatch per frame.

    Donation contract (the reason this is its own class and not N stacked
    Pipelines): the flat carries tuple and the input wire parts stay
    donation-safe — each dispatch consumes them (``donate=True`` donates the
    carries; :meth:`donation_mask` is the widest sound per-argnum mask). The
    producer BOUNDARY value is multiply-consumed (every branch reads it), so
    it is never threaded through as a donated argument: it rides the carry of
    a ``devchain_boundary`` fence stage, which makes it a program OUTPUT
    root — XLA materializes exactly the value the standalone producer would
    have produced (the fused-vs-actor bit-equality contract) and the donation
    analysis never sees it as an aliasable input.

    Duck-types the :class:`Pipeline` surface the TPU kernel blocks consume
    (``in_dtype``/``stages``/``frame_multiple``/``init_carry``/``fn``/
    ``wired_fn``/``compile``/``compile_wired``/``update_stage``), with the
    single-output fields generalized per branch: ``out_dtypes[j]``,
    ``path_ratios[j]`` (producer·branch rate), ``branch_out_items(j, n)``.
    ``stages`` is the FLAT concatenation (producer then branches in order),
    which is also the carry layout — ``update_stage`` addresses it exactly
    like a linear pipeline's (the devchain ctrl-retune contract).
    """

    def __init__(self, producer_stages: Sequence[Stage],
                 branch_stage_lists: Sequence[Sequence[Stage]], in_dtype,
                 optimize: bool = True):
        if not branch_stage_lists or len(branch_stage_lists) < 2:
            raise ValueError("FanoutPipeline needs >= 2 branches "
                             "(use Pipeline for linear chains)")
        self.in_dtype = np.dtype(in_dtype)
        # the AS-GIVEN stage lists, before any LTI merging: the streamed-pick
        # cache records a signature from these too, so a devchain-composed
        # region (per-member optimized names) still finds the pick when the
        # caller's optimize=True merged stages across member boundaries
        self.raw_stage_lists = (list(producer_stages),
                                [list(bs) for bs in branch_stage_lists])
        self.producer = Pipeline(list(producer_stages), in_dtype,
                                 optimize=optimize)
        self.branches = [Pipeline(list(bs), self.producer.out_dtype,
                                  optimize=optimize)
                         for bs in branch_stage_lists]
        self.stages = list(self.producer.stages)
        for b in self.branches:
            self.stages.extend(b.stages)
        # input-frame contract: the lcm of every producer→branch path's
        # requirement (each path is a linear pipeline; reuse its math)
        fm = self.producer.frame_multiple
        for b in self.branches:
            path = Pipeline(self.producer.stages + b.stages, in_dtype,
                            optimize=False)
            fm = int(np.lcm(fm, path.frame_multiple))
        self.frame_multiple = fm
        self.path_ratios = [self.producer.ratio * b.ratio
                            for b in self.branches]
        self.out_dtypes = [b.out_dtype for b in self.branches]
        self.n_branches = len(self.branches)
        # single-output compatibility surface (wire picking / link budgeting):
        # total output items per input item, and the first branch's dtype
        self.ratio = sum(self.path_ratios, Fraction(0, 1))
        self.out_dtype = self.out_dtypes[0]
        self._fn = None
        self._wired_fns = {}

    def branch_out_items(self, branch: int, in_items: int) -> int:
        q = Fraction(in_items) * self.path_ratios[branch]
        assert q.denominator == 1, (in_items, self.path_ratios[branch])
        return int(q)

    def out_items(self, in_items: int) -> int:
        """TOTAL items across branches per ``in_items`` inputs (the linear
        surface; per-branch counts come from :meth:`branch_out_items`)."""
        q = Fraction(in_items) * self.ratio
        assert q.denominator == 1
        return int(q)

    def init_carry(self):
        """Flat carries: producer slots then each branch's, matching
        ``self.stages`` (the ``update_stage`` addressing contract)."""
        out = list(self.producer.init_carry())
        for b in self.branches:
            out.extend(b.init_carry())
        return tuple(out)

    def fn(self):
        """``run(carries, x) -> (carries, (y_0, …, y_{N-1}))``: the producer
        output is computed once and consumed by every branch in-program."""
        if self._fn is None:
            n_p = len(self.producer.stages)
            pfn = self.producer.fn()
            bfns = [b.fn() for b in self.branches]
            sizes = [len(b.stages) for b in self.branches]

            def run(carries, x):
                pc, mid = pfn(tuple(carries[:n_p]), x)
                new_c, outs, off = list(pc), [], n_p
                for bf, sz in zip(bfns, sizes):
                    bc, y = bf(tuple(carries[off:off + sz]), mid)
                    new_c.extend(bc)
                    outs.append(y)
                    off += sz
                return tuple(new_c), tuple(outs)

            self._fn = run
        return self._fn

    def part_counts(self, wire) -> tuple:
        """Wire parts PER BRANCH of the wired form's flat output (a quantizing
        wire ships payload + scale; f32/bf16 ship one part) — the re-nesting
        key for drain loops consuming the flat part tuple."""
        from .wire import get_wire
        wire = get_wire(wire)
        return tuple(wire.part_count(dt) for dt in self.out_dtypes)

    def in_part_count(self, wire) -> int:
        from .wire import get_wire
        return get_wire(wire).part_count(self.in_dtype)

    def wired_fn(self, wire, k: int = 1, words: bool = False):
        """The fan-out DAG with the wire codec's decode PROLOG fused in and
        one encode EPILOG per branch: ``(carries, *in_parts) -> (carries,
        flat_out_parts)`` where the flat tuple concatenates each branch's
        parts in branch order (:meth:`part_counts` gives the split). ``k > 1``
        is the megabatch scan form and ``words`` the decode from 32-bit
        words, exactly as :meth:`Pipeline.wired_fn`."""
        from .wire import get_wire
        wire = get_wire(wire)
        key = (wire.name, int(k)) + (("words",) if words else ())
        if key not in self._wired_fns:
            inner = self.fn()
            in_dt, w = self.in_dtype, wire
            decode = w.decode_words_jax if words else w.decode_jax

            def run(carries, *parts):
                with jax.named_scope("wire_decode"):
                    x = decode(parts, in_dt)
                carries, ys = inner(carries, x)
                flat = []
                with jax.named_scope("wire_encode"):
                    for y in ys:
                        flat.extend(w.encode_jax(y))
                return carries, tuple(flat)

            if k == 1:
                self._wired_fns[key] = run
            else:
                def run_scan(carries, *parts):
                    def body(c, p):
                        return run(c, *p)
                    return jax.lax.scan(body, carries, tuple(parts))

                self._wired_fns[key] = run_scan
        return self._wired_fns[key]

    def donation_mask(self, wire) -> tuple:
        """The WIDEST sound wired donation mask: the carries AND the input
        wire parts (every argument is single-consumer per dispatch). The
        producer boundary value is NOT in this set by construction — it is a
        program output root (class docstring), so the mask can never alias a
        multiply-consumed value. Opt-in (``compile_wired(donate=mask)``)
        rather than the default: XLA only profits when an input part's
        shape/dtype matches an output's, and warns otherwise."""
        return (0,) + tuple(range(1, 1 + self.in_part_count(wire)))

    # compile/compile_wired/update_stage are the linear pipeline's own
    # methods, borrowed: they touch only the duck-typed surface this class
    # implements (frame_multiple / fn / wired_fn / init_carry / stages, with
    # the flat carry layout matching self.stages by construction), so one
    # implementation serves both and can never diverge. The fan-out-specific
    # donation story lives in :meth:`donation_mask` — pass it as
    # ``compile_wired(donate=...)`` for the widest sound mask (carries +
    # input frame parts; the multiply-consumed boundary value can never
    # appear in any mask because it is not an argument).
    compile = Pipeline.compile
    compile_wired = Pipeline.compile_wired
    packed_wired_fn = Pipeline.packed_wired_fn
    pair_word_slots = Pipeline.pair_word_slots
    update_stage = Pipeline.update_stage
    # carry checkpointing borrows too: the FLAT carries tuple (producer then
    # branches) is an ordinary pytree, so snapshot/validate/restore of the
    # composed fan-out carry is exactly the linear pipeline's contract — one
    # checkpoint covers every branch's state at once (per-branch replay
    # cursors live in the kernel's drain bookkeeping, not the carry)
    snapshot_carry = Pipeline.snapshot_carry
    carry_matches = Pipeline.carry_matches
    restore_carry = Pipeline.restore_carry


class DagPipeline:
    """A general device-plane stage DAG compiled as ONE multi-output program.

    The explicit node/edge generalization of :class:`FanoutPipeline`: nested
    fan-out (a node's value consumed by several nodes, at ANY depth), fan-IN
    (a node whose first stage is a :class:`MergeStage` over K ordered input
    nodes), and their closure — the diamond ``producer → broadcast →
    branches → merge`` — all collapse into one XLA program whose outputs are
    the DAG's SINK set. This is the compute plane of the whole-receiver
    fusion pass (``runtime/devchain.py``): a ``sync → {demod, chan-est} →
    decode`` region becomes one dispatch per frame with zero interior
    host↔device traffic (the whole-program handoff of arXiv:1810.09868).

    ``nodes`` is a sequence of ``(stage_list, input_ids)`` in TOPOLOGICAL
    order: node 0 is the root (``input_ids == []``, reads the program input);
    every other node lists the node indices feeding it (all ``< i``). A node
    with several inputs must START with a ``MergeStage(k == len(inputs))``;
    plain stages compose linearly after it. Sinks (nodes no other node
    consumes, in index order) are the program outputs.

    Donation contract — exactly :class:`FanoutPipeline`'s, generalized: the
    flat carries and the input wire parts are donation-safe
    (:meth:`donation_mask`); any MULTIPLY-consumed interior value is a node
    output read by several nodes, which is never a program argument, so no
    donation mask can alias it. The devchain builder additionally pins every
    such value (and every member boundary) to standalone numerics with a
    carry-stash ``devchain_boundary`` fence — a program output root.

    Rate contracts: each sink ``j`` carries ``path_ratios[j]`` (output items
    per region-input item — through a merge this SUMS the joined branches in
    ``concat`` mode) and ``tag_ratios[j]`` (the tag-index remap along the
    PRIMARY chain: merges contribute only their own ``ratio``, because tags
    ride input 0 — see :class:`MergeStage`). ``mode="equal"`` merges whose
    input paths arrive at different rates raise ``ValueError`` at
    construction (the devchain finder declines such regions honestly).

    Duck-types the fan-out surface the TPU kernel blocks consume
    (``n_branches``/``path_ratios``/``out_dtypes``/``branch_out_items``/
    ``part_counts``/``in_part_count``/``wired_fn``/``donation_mask`` plus the
    linear compile/checkpoint surface), with ``stages`` the FLAT node-order
    concatenation — also the carry layout, so ``update_stage`` addressing and
    carry checkpointing work exactly as on a linear pipeline.
    """

    def __init__(self, nodes, in_dtype, optimize: bool = False):
        if not nodes:
            raise ValueError("DagPipeline needs at least one node")
        self.in_dtype = np.dtype(in_dtype)
        self.raw_nodes = [(list(sl), tuple(int(j) for j in inputs))
                          for sl, inputs in nodes]
        consumed: dict = {}
        for i, (_sl, inputs) in enumerate(self.raw_nodes):
            if i == 0:
                if inputs:
                    raise ValueError("node 0 is the root and takes the "
                                     "program input (input_ids must be [])")
            elif not inputs:
                raise ValueError(f"node {i} has no inputs (one root only)")
            for j in inputs:
                if not 0 <= j < i:
                    raise ValueError(
                        f"node {i} input {j} violates topological order")
                consumed[j] = consumed.get(j, 0) + 1
        self.sinks = [i for i in range(len(self.raw_nodes))
                      if i not in consumed]
        # -- per-node stage lists (optionally LTI-merged per linear segment) --
        self._nodes: list = []           # (stages, inputs, carry_offset)
        self.stages: list = []
        # -- rate/dtype walk: r = items per region-input item in front of the
        # value; fm accumulates the region-input frame multiple exactly like
        # Pipeline's scan, but per DAG path --
        fm = 1
        node_r: list = []                # per node: output rate
        node_dt: list = []               # per node: output dtype
        node_tag_r: list = []            # per node: primary-chain tag remap
        for i, (sl, inputs) in enumerate(self.raw_nodes):
            stages = list(sl)
            if len(inputs) > 1:
                if not stages or not isinstance(stages[0], MergeStage):
                    raise ValueError(
                        f"node {i} joins {len(inputs)} inputs but does not "
                        f"start with a MergeStage")
                m = stages[0]
                if m.k != len(inputs):
                    raise ValueError(
                        f"node {i}: MergeStage k={m.k} != {len(inputs)} "
                        f"inputs")
                in_rs = [node_r[j] for j in inputs]
                in_dts = {np.dtype(node_dt[j]) for j in inputs}
                if len(in_dts) != 1:
                    raise ValueError(
                        f"node {i}: merge inputs disagree on dtype "
                        f"({sorted(str(d) for d in in_dts)})")
                for r_i in in_rs:
                    need = Fraction(m.frame_multiple, 1) / r_i
                    fm = int(np.lcm(fm, need.numerator))
                if m.mode == "equal":
                    if len(set(in_rs)) != 1:
                        raise ValueError(
                            f"node {i}: equal-mode merge rate contract "
                            f"violated (input path rates {in_rs})")
                    r = in_rs[0] * m.ratio
                else:                    # concat: output counts every input
                    r = sum(in_rs, Fraction(0, 1)) * m.ratio
                fm = int(np.lcm(fm, r.denominator))
                dt = np.dtype(m.out_dtype) if m.out_dtype is not None \
                    else in_dts.pop()
                tag_r = node_tag_r[inputs[0]] * m.ratio
                rest = stages[1:]
            else:
                r = node_r[inputs[0]] if inputs else Fraction(1, 1)
                dt = np.dtype(node_dt[inputs[0]]) if inputs \
                    else self.in_dtype
                tag_r = node_tag_r[inputs[0]] if inputs else Fraction(1, 1)
                m = None
                rest = stages
            if any(isinstance(s, MergeStage) for s in rest):
                raise ValueError(
                    f"node {i}: a MergeStage may only be a multi-input "
                    f"node's FIRST stage")
            if optimize and rest:
                rest = _merge_lti(rest, dt)
            for s in rest:
                need = Fraction(s.frame_multiple, 1) / r
                fm = int(np.lcm(fm, need.numerator))
                r *= s.ratio
                tag_r *= s.ratio
                fm = int(np.lcm(fm, r.denominator))
                if s.out_dtype is not None:
                    dt = np.dtype(s.out_dtype)
            node_r.append(r)
            node_dt.append(dt)
            node_tag_r.append(tag_r)
            final = ([m] if m is not None else []) + list(rest)
            self._nodes.append((final, tuple(inputs), len(self.stages)))
            self.stages.extend(final)
        self.frame_multiple = fm
        self.node_ratios = list(node_r)
        self.node_dtypes = list(node_dt)
        # -- fan-out-compatible sink surface ---------------------------------
        self.n_branches = len(self.sinks)
        self.path_ratios = [node_r[s] for s in self.sinks]
        self.tag_ratios = [node_tag_r[s] for s in self.sinks]
        self.out_dtypes = [node_dt[s] for s in self.sinks]
        # per sink: does its path cross a concat-mode merge? A concat output
        # interleaves its inputs' FULL frames back to back, so a partial
        # (EOS-tail) input frame cannot be represented by a valid-prefix
        # count — such sinks emit only full frames (the kernels' drain clamps
        # a partial group's valid to 0; same rule as TpuMergeStage's actor
        # path), which stays inside the devchain EOS-tail divergence contract
        crossed = []
        for i, (_sl, inputs) in enumerate(self.raw_nodes):
            c = any(crossed[j] for j in inputs)
            first = self._nodes[i][0][0] if self._nodes[i][0] else None
            if isinstance(first, MergeStage) and first.mode == "concat":
                c = True
            crossed.append(c)
        self.concat_sinks = [crossed[s] for s in self.sinks]
        self.ratio = sum(self.path_ratios, Fraction(0, 1))
        self.out_dtype = self.out_dtypes[0]
        self._fn = None
        self._wired_fns = {}

    def init_carry(self):
        """Flat carries in node order, matching ``self.stages`` (the
        ``update_stage`` / checkpoint addressing contract)."""
        carries = []
        for i, (stages, inputs, _off) in enumerate(self._nodes):
            dt = self.in_dtype if not inputs \
                else np.dtype(self.node_dtypes[inputs[0]])
            for s in stages:
                carries.append(s.init_carry(dt))
                if s.out_dtype is not None:
                    dt = np.dtype(s.out_dtype)
        return tuple(carries)

    def fn(self):
        """``run(carries, x) -> (carries, (y_sink0, …))``: every interior
        edge stays in-program — a multiply-consumed node output feeds each
        consumer without rematerialization, a merge node reads its K input
        values as one tuple."""
        if self._fn is None:
            nodes = self._nodes
            sinks = self.sinks

            def run(carries, x):
                new_c = list(carries)
                vals: list = [None] * len(nodes)
                for i, (stages, inputs, off) in enumerate(nodes):
                    if not inputs:
                        v = x
                    elif len(inputs) == 1:
                        v = vals[inputs[0]]
                    else:
                        v = tuple(vals[j] for j in inputs)
                    for si, s in enumerate(stages):
                        with jax.named_scope(s.name):
                            c, v = s.fn(carries[off + si], v)
                        new_c[off + si] = c
                    vals[i] = v
                return tuple(new_c), tuple(vals[s] for s in sinks)

            self._fn = run
        return self._fn

    # the per-sink item math, flat multi-output wired form, donation mask and
    # the linear compile/checkpoint surface are exactly the fan-out
    # pipeline's — the sink tuple quacks like the branch tuple (part_counts
    # gives the split)
    branch_out_items = FanoutPipeline.branch_out_items
    out_items = FanoutPipeline.out_items
    part_counts = FanoutPipeline.part_counts
    in_part_count = FanoutPipeline.in_part_count
    wired_fn = FanoutPipeline.wired_fn
    donation_mask = FanoutPipeline.donation_mask
    compile = Pipeline.compile
    compile_wired = Pipeline.compile_wired
    packed_wired_fn = Pipeline.packed_wired_fn
    pair_word_slots = Pipeline.pair_word_slots
    update_stage = Pipeline.update_stage
    snapshot_carry = Pipeline.snapshot_carry
    carry_matches = Pipeline.carry_matches
    restore_carry = Pipeline.restore_carry


def _merge_lti(stages: Sequence[Stage], in_dtype) -> list:
    """Peephole pass: collapse runs of adjacent LTI FIR stages into ONE overlap-save.

    A cascade of FIRs is itself an FIR with the convolved taps; filtering after a
    decimator by ``d`` equals filtering with the taps zero-stuffed by ``d`` before it
    (noble identity), so ``(t1, d1) · (t2, d2) → (t1 * stuff(t2, d1), d1·d2)``. On the
    device this is the big fusion win: N stage cascades cost ONE FFT pass instead of N
    (the reference pays per-block dispatch here, ``perf/fir/fir.rs:49-95``).

    The stream dtype is tracked through the chain: on a REAL stream each FIR stage
    takes ``.real`` at its boundary, so complex-tap runs only merge where the stream
    is complex at that position.
    """
    out: list = []
    dtype = np.dtype(in_dtype)
    out_dtypes: list = []               # stream dtype ENTERING each stage in `out`
    for s in stages:
        if s.lti is not None and out and out[-1].lti is not None:
            t1, d1, fl1, im1 = out[-1].lti
            t2, d2, fl2, im2 = s.lti
            # per-call-site route pins (fft_impl, precision): merge only when
            # both sides agree — a merged stage can honor ONE pin set, and
            # silently dropping a pin would revert the stage to the module
            # policy / f32, defeating exactly what the pin bought
            p1 = (out[-1].route or (None, None, None))[1:]
            p2 = (s.route or (None, None, None))[1:]
            complex_stream = bool(np.issubdtype(out_dtypes[-1], np.complexfloating))
            if p1 != p2 or (not complex_stream
                            and not (np.isrealobj(t1) and np.isrealobj(t2))):
                # (real streams take .real at EACH stage boundary; merging
                # complex-tap cascades would change that — only safe on
                # complex streams)
                out.append(s)
                out_dtypes.append(dtype)
                if s.out_dtype is not None:
                    dtype = np.dtype(s.out_dtype)
                continue
            if d1 == 1:
                taps = np.convolve(t1, t2)
            else:
                up = np.zeros((len(t2) - 1) * d1 + 1, dtype=np.result_type(t1, t2))
                up[::d1] = t2
                taps = np.convolve(t1, up)
            # an explicit "os" on either side pins the merged numerics; "pallas"/
            # "poly" survive only if both sides forced them (and the merged taps
            # allow it) — a force must not silently downgrade to "auto"
            impl = "os" if "os" in (im1, im2) else \
                ("pallas" if im1 == im2 == "pallas" else
                 ("poly" if im1 == im2 == "poly" else "auto"))
            out[-1] = fir_stage(taps, decim=d1 * d2, fft_len=max(fl1, fl2),
                                name=f"{out[-1].name}*{s.name}", impl=impl,
                                fft_impl=p1[0], precision=p1[1])
            # stream dtype entering the merged stage is unchanged; FIR stages keep the
            # stream dtype so `dtype` needs no update here
        else:
            out.append(s)
            out_dtypes.append(dtype)
            if s.out_dtype is not None:
                dtype = np.dtype(s.out_dtype)
    return out


# ---------------------------------------------------------------------------
# stage factories
# ---------------------------------------------------------------------------

def _param_to_device(arr, dev):
    """Upload a retuned carry parameter from an ``update`` hook: through the SAME
    uncommitted placement ``init_carry`` uses, then committed beside the live
    carry. jit caches key on committedness, so handing ``dev`` straight to
    ``to_device`` re-lowered the pair shim's join program on the first complex
    retune (measured on the v5e: one ``jit(<lambda>)`` compile inside the ctrl
    handler) — this way a retune compiles nothing."""
    from .xfer import to_device
    x = to_device(arr)
    return jax.device_put(x, dev) if dev is not None else x


def _pallas_fir_wins(nt: int, is_complex: bool) -> bool:
    """Trace-time choice of the direct pallas FIR over FFT overlap-save.

    The rule comes from the one on-chip A/B on record (v5e, 2026-07, frame
    512k, scan-marginal rates): at 16 real taps the pallas kernel was 3.3x
    over overlap-save; the advantage decayed with tap count and the crossover
    sat between 48 (pallas +12%) and 64 (OS +17%). Complex frames pay two real
    passes: a tie at 16 taps, OS-favored by 32 — at a tie OS wins (one pass,
    no split). Hence real <= 48, complex never. That A/B predates most of the
    tree; both routes compile and match on the chip (chip_smoke.py), the
    crossover itself is "not measured" on the current code (ROADMAP D9).
    """
    if jax.default_backend() != "tpu":
        return False
    return (not is_complex) and nt <= 48


def fir_stage(taps, decim: int = 1, fft_len: int = 8192, name: str = "fir",
              impl: str = "auto", fft_impl: Optional[str] = None,
              precision: Optional[str] = None) -> Stage:
    """FFT overlap-save FIR (+ optional decimation) as a jitted stage.

    History carry = last ``ntaps-1`` inputs (the `min_items` overlap of `fir.rs:49`
    reframed for frames, SURVEY §5 long-context note). The frame is blocked into
    ``fft_len`` segments with hop ``L = fft_len - (ntaps-1)`` and filtered in the
    frequency domain — batched 2D FFTs are the TPU-idiomatic FIR (direct time-domain
    convolution compiles poorly at SDR frame sizes on the TPU backend). The
    frequency-domain taps ride in the carry (identity pass-through under XLA
    input-output aliasing), which also makes them donation-safe and hot-swappable.

    ``impl``: "auto" additionally routes short real-tap filters to the direct pallas
    kernel on TPU (see :func:`_pallas_fir_wins`), and decimating filters with modest
    per-output work to the polyphase-decimation einsum (see below); "os" forces
    overlap-save; "pallas" forces the direct kernel (CI exercises it in interpret
    mode); "poly" forces the decimating einsum.

    Polyphase decimation (``decim > 1``): computing the full-rate convolution and
    slicing ``y[::D]`` wastes (D-1)/D of the FLOPs. The decimated output is
    ``y[q] = Σ_t taps[t] · x[q·D − t]`` — windows of ``ntaps`` samples at stride D,
    which (like :func:`resample_stage`'s poly path) are shifted views of the input
    reshaped into MXU-sized rows, each contracted against a band matrix of the taps
    (:func:`_poly_decim_fir_stage`); the stage's frame multiple drops from
    lcm(hop, D) to D.
    Matches ``decimate == true`` FIR cores (``futuredsp/fir.rs:31``) re-designed for
    the MXU rather than translated.

    ``fft_impl`` pins the overlap-save core's FFT implementation PER CALL SITE
    (``mxu_fft.fft(impl=…)``): the module ``set_impl`` policy binds at trace time
    and jit caches keep whichever path was bound first, so a per-stage pin is
    the only way two chains in one process can hold different FFT routes
    (the plumbing promised in the ``ops/mxu_fft.py`` header).

    ``precision="bf16"`` builds the interior-precision-lowered variant
    (``ops/precision.py``): bf16 MXU passes in the overlap-save FFTs, bf16
    tap/accumulation in the pallas and polyphase kernels (carried weights land
    in bf16). ``precision="int8"`` (real taps only) abandons the FFT form
    entirely — no useful int8 FFT exists — and runs the convolution as a
    banded windowed matmul: the frame blocks into ``Bq``-sample tiles
    (each with its left neighbour, the overlap-save trick in the time
    domain), both operands absmax-quantized to int8 in-trace, one
    ``[2Bq]·[2Bq, Bq]`` int8 matmul with int32 accumulation per tile. The
    band matrix is built from the CARRIED taps so runtime swaps reach it, and
    the carry tree (spectrum, taps, tail) is bit-compatible with the f32
    stage — the serve brownout's leaf conversion and the checkpoint leaf
    contract both depend on that. The f32-built stage exposes both lowerings
    through its ``Stage.lower`` hook — the SNR-budgeted pass uses that.
    """
    assert impl in ("auto", "os", "pallas", "poly"), impl
    taps = np.asarray(taps)
    nt = len(taps)
    built_real = np.isrealobj(taps)     # baked into the traced branches; the update
    #                                     hook refuses swaps that would change it
    # auto cap nt/D ≤ 32: the poly window matrix materializes ~nt/D × the frame in
    # HBM, so the route stays where both the MACs/input and the intermediate are
    # modest; longer filters keep the OS path's fixed fft_len working set.
    # An explicit pallas force on a DECIMATING filter routes through the poly
    # factorization too — its fused FIR→decimate kernel (pallas_poly_fir)
    # computes at the decimated rate instead of full-rate-then-slice.
    if impl == "poly" or (impl == "pallas" and decim > 1) \
            or (impl == "auto" and decim > 1 and nt <= 32 * decim):
        return _poly_decim_fir_stage(taps, decim, fft_len, name, impl,
                                     precision=precision)
    if impl == "pallas":
        # an explicit force must not silently no-op: the kernel is real-taps-only
        assert np.isrealobj(taps) and nt >= 2, \
            "impl='pallas' requires >= 2 real taps (complex taps: use the OS path)"
    # 50% overlap-save with power-of-two hop L and fft_len = 2L: radix-friendly FFTs and
    # power-of-two frame multiples (at the cost of carrying L instead of ntaps-1 samples).
    L = fft_len // 2
    while L < 2 * nt:                   # hop must comfortably exceed the tap overlap
        L *= 2
    fft_len = 2 * L
    if precision == "int8":
        assert built_real, "precision='int8' requires real taps"
    # int8 banded-matmul tile: a power of two dividing the hop L (frames are
    # L-multiples, so they block evenly) that covers the tap overlap in one
    # left-neighbour tile (Bq >= nt-1; pow2ceil(nt-1) <= L since L >= 2*nt)
    Bq = min(L, 128)
    while Bq < nt - 1:
        Bq *= 2

    def _spectra(t):
        # full spectrum, and the real-input half spectrum (real inputs discard the
        # imaginary response, so conv(x, t).real == conv(x, t.real) — same semantics)
        full = np.fft.fft(np.concatenate([t, np.zeros(fft_len - nt)])
                          ).astype(np.complex64)
        half = np.fft.rfft(np.concatenate([np.real(t), np.zeros(fft_len - nt)])
                           ).astype(np.complex64)
        return full, half

    H, Hr = _spectra(taps)

    fft_prec = "bf16" if precision == "bf16" else None

    def fn(carry, x):
        Hc, tt, tail = carry
        if precision == "int8":
            # int8 ladder rung: banded windowed matmul over Bq-sample tiles.
            # T[j, i] = taps[Bq + i − j], so tile s's output
            # y[s·Bq + i] = Σ_j ext8[s·Bq + j] · T[j, i] = Σ_k taps[k]·x[s·Bq+i−k]
            # with ext8 carrying Bq history samples in front (Bq >= nt−1).
            jj = jnp.arange(2 * Bq)[:, None]
            ii = jnp.arange(Bq)[None, :]
            kk = Bq + ii - jj
            T = jnp.where((kk >= 0) & (kk < nt),
                          tt[jnp.clip(kk, 0, nt - 1)], 0.0)
            sw = jnp.maximum(jnp.max(jnp.abs(tt)), 1e-30) / 127.0
            Tq = jnp.round(T / sw).astype(jnp.int8)

            def _conv(plane):
                sx = jnp.maximum(jnp.max(jnp.abs(plane)), 1e-30) / 127.0
                q = jnp.round(plane / sx).astype(jnp.int8)
                rq = q.reshape(-1, Bq)                      # [S+1, Bq]
                blk = jnp.concatenate([rq[:-1], rq[1:]], axis=1)   # [S, 2Bq]
                acc = jnp.matmul(blk, Tq,
                                 preferred_element_type=jnp.int32)
                return acc.reshape(-1).astype(jnp.float32) * (sx * sw)

            ext8 = jnp.concatenate([tail[L - Bq:], x])
            if jnp.iscomplexobj(x):
                y = jax.lax.complex(_conv(ext8.real), _conv(ext8.imag))
            else:
                y = _conv(ext8)
            y = y.astype(x.dtype)
            if decim > 1:
                y = y[::decim]
            # frames are >= L samples (frame_multiple), so the new tail is
            # the frame's own last L samples
            return (Hc, tt, x[x.shape[0] - L:]), y
        ext = jnp.concatenate([tail, x])             # [(S+1)·L], S = n // L
        is_c = jnp.iscomplexobj(x)
        if impl != "os" and np.isrealobj(taps) and nt >= 2 and (
                impl == "pallas" or _pallas_fir_wins(nt, is_c)):
            from .pallas_kernels import pallas_fir_continue
            # time-domain taps come from the CARRY (not the closure) so a runtime
            # tap swap reaches the pallas path too — same shape, no recompile
            y = pallas_fir_continue(ext[L - (nt - 1):L], x, tt,
                                    precision=precision)
            if decim > 1:
                y = y[::decim]
            return (Hc, tt, ext[ext.shape[0] - L:]), y
        # block s = ext[sL : sL+2L] = rows[s] ++ rows[s+1]: built from two strided
        # slices + concat, NOT a gather — TPU gathers run ~9× slower than this form
        rows = ext.reshape(-1, L)
        blocks = jnp.concatenate([rows[:-1], rows[1:]], axis=1)   # [S, 2L]
        if jnp.iscomplexobj(x):
            spec = mxu_fft.fft(blocks, precision=fft_prec,
                               impl=fft_impl) * Hc[None, :]
            seg = mxu_fft.ifft(spec, precision=fft_prec,
                               impl=fft_impl)[:, L:]   # linear-conv region
        elif Hc.shape[0] == fft_len:
            # real input with a full-spectrum carry (chosen at init_carry time when the
            # MXU policy was active — the four-step has no half-spectrum variant; it
            # still beats the XLA rfft). Branching on the carry shape keeps fn and
            # carry coherent even if the policy flips between init and trace.
            spec = mxu_fft.fft(blocks.astype(jnp.complex64), precision=fft_prec,
                               impl=fft_impl) * Hc[None, :]
            seg = mxu_fft.ifft(spec, precision=fft_prec, impl=fft_impl)[:, L:].real
        else:
            spec = jnp.fft.rfft(blocks, axis=1) * Hc[None, :]
            seg = jnp.fft.irfft(spec, n=fft_len, axis=1)[:, L:]
        y = seg.reshape(-1).astype(x.dtype)
        if decim > 1:
            y = y[::decim]
        return (Hc, tt, ext[ext.shape[0] - L:]), y

    def init_carry(dtype):
        dt = np.dtype(dtype)
        use_full = (np.issubdtype(dt, np.complexfloating)
                    or mxu_fft._use_mxu(fft_len, fft_impl))
        Hsel = H if use_full else Hr
        # complex H2D (incl. eager jnp.zeros, which is a host device_put!) rides
        # the pair shim, see ops/xfer.py
        from .xfer import to_device
        return (to_device(Hsel), to_device(np.real(taps).astype(np.float32)),
                to_device(np.zeros(L, dtype=dt)))

    def update(carry, taps=None):
        """Swap the filter while frames are in flight: same tap COUNT (shapes are
        static under jit), new response. Rebuilds the spectrum matching the carry's
        layout (full vs half, inferred from the carried H's length) and the
        time-domain taps the pallas branch reads; history is preserved, so the
        transition is seamless after nt-1 samples. New arrays land on the device
        the carry lives on."""
        if taps is None:
            return carry
        new = np.asarray(taps)
        if len(new) != nt:
            raise ValueError(
                f"tap swap must keep the tap count ({nt}); got {len(new)} — "
                f"rebuild the stage for a different filter length")
        if np.iscomplexobj(new) and built_real:
            # realness is baked at trace time (pallas branch, half-spectrum path);
            # a complex swap on a real-built stage would silently drop .imag there
            raise ValueError(
                "stage was built with real taps; swapping to complex taps "
                "requires rebuilding the stage")
        Hc_old, _tt, tail = carry
        full, half = _spectra(new)
        dev = next(iter(tail.devices())) if isinstance(tail, jax.Array) else None
        Hn = full if Hc_old.shape[0] == fft_len else half
        return (_param_to_device(Hn, dev),
                _param_to_device(np.real(new).astype(np.float32), dev), tail)

    # frame must be a multiple of the hop (and of decim at the output side)
    multiple = int(np.lcm(L, decim))

    def _lower(p: str) -> Optional[Stage]:
        if p == "bf16" or (p == "int8" and built_real):
            return fir_stage(taps, decim=decim, fft_len=fft_len, name=name,
                             impl=impl, fft_impl=fft_impl, precision=p)
        return None

    return Stage(fn, init_carry, Fraction(1, decim), None, multiple, name,
                 lti=(taps, decim, fft_len, impl), update=update,
                 lower=_lower,
                 compute_dtype=(precision if precision in ("bf16", "int8")
                                else "f32"),
                 route=(impl, fft_impl, precision))


def _int8_shifted_matvec(rows, W, m: int, nq: int):
    """The int8 ladder rung of :func:`_shifted_matvec` (real planes only):
    dynamic absmax quantization of BOTH operands (scale = absmax/127 — the
    standard symmetric int8 scheme), every shifted MAC on the int8 matmul
    path with int32 accumulation, one dequantize at the sink. The scales are
    data-derived in-trace, so the carried weight matrix stays float32 and the
    carry tree is bit-compatible with the f32 stage (the serve brownout's
    leaf-wise ``astype`` conversion and the checkpoint leaf contract both
    rely on that — see ops/precision.py)."""
    from functools import partial as _partial
    sw = jnp.maximum(jnp.max(jnp.abs(W)), 1e-30) / 127.0
    Wq = jnp.round(W / sw).astype(jnp.int8)
    sx = jnp.maximum(jnp.max(jnp.abs(rows)), 1e-30) / 127.0
    rq = jnp.round(rows / sx).astype(jnp.int8)
    mm = _partial(jnp.matmul, preferred_element_type=jnp.int32)
    acc = mm(rq[m:m + nq], Wq[0])
    for r in range(1, m + 1):
        acc = acc + mm(rq[m - r:m - r + nq], Wq[r])
    return acc.astype(jnp.float32) * (sx * sw)


_MXU_EDGE = 128     # contraction width that fills a v5e MXU pass


def _row_width(D: int) -> int:
    """Row width ``R = g·D`` of the shifted-row factorization for decimation ``D``
    (see :func:`_shifted_matvec`). Rows of width D contract over D: at D = 4 the
    FM tuner's 128 taps were 33 complex ``[16375, 4]·[4]`` matvecs per lane, which
    XLA:TPU lowers to 99 VPU multiply-reduces over a minor dimension that fills 4
    of 128 lanes (v5e, [64, 65500]: 13.3 ms). D >= 64 is already MXU-shaped (the
    audio resampler's ``[nq, 125]·[125, 24]``) and keeps its rows; smaller D takes
    the smallest multiple of D that is >= 128, the frame's tail zero-padded to a
    whole row. The chip A/B of that stage (v5e, PERF.md section 6, PR 25): R = 128
    0.36 ms, 256 0.45, 100 (divides the frame; three terms, K unaligned) 0.92,
    500 (divides it; 131 rows, 4x the operations) 1.3-1.6, so the rule does not
    look for a divisor of the frame."""
    return D if 2 * D >= _MXU_EDGE else D * -(-_MXU_EDGE // D)


def _band_index(m: int, D: int, R: int) -> np.ndarray:
    """Static index table of :func:`_band_weights`: ``idx[a, s, o]`` is the flat
    position in ``W[m+1, D]`` of the weight that row shift ``a``, column ``s`` of
    an R-wide row contributes to that row's output ``o``, or ``(m+1)·D`` (a zero
    appended to the flat weights) where no tap falls."""
    g = R // D
    a = np.arange(-(-m // g) + 1)[:, None, None]
    s = np.arange(R)[None, :, None]
    o = np.arange(g)[None, None, :]
    u = a * R - s                       # row offset a·R − s = (a'−o)·D − s'
    q = -(-u // D)
    ap, sp = o + q, q * D - u
    return np.where((ap >= 0) & (ap <= m), ap * D + sp, (m + 1) * D).astype(np.int32)


def _band_weights(W, m: int, R: int):
    """Re-block the carried D-wide weights ``W[m+1, D(, I)]`` into the band matrix
    ``A[(m_R+1)·R, g(·I)]`` over rows of width ``R = g·D``, ``m_R = ceil(m/g)``:
    each R-wide row yields g outputs (× I phases), and block ``a`` of A is
    ``A[a][s, o] = W[a', s']`` with ``a'·D − s' = a·R + o·D − s`` — for a plain
    decimator ``A[a][s, o] = c[a·R + D·o − s]``, zero outside the taps. Built IN
    THE TRACE from the carry through a static index table (as ``fir_stage``'s
    int8 rung builds its band matrix), so the carry tree stays the D-wide one:
    retunes, tap swaps, page gather/scatter and persisted carries see no change,
    and a per-lane ``W`` under ``vmap`` gives per-lane bands. Returned with a
    leading axis of one: the weights of a single term of :func:`_shifted_matvec`."""
    tail = W.shape[2:]
    idx = _band_index(m, W.shape[1], R)
    Wz = jnp.concatenate([W.reshape((-1,) + tail), jnp.zeros((1,) + tail, W.dtype)])
    return Wz[idx].reshape(1, idx.shape[0] * R, -1)


def _shifted_matvec(ext: jnp.ndarray, W, m: int, nq: int,
                    precision: Optional[str] = None):
    """``y = Σ_{r=0..m} rows[m−r : m−r+nq] @ W[r]`` with ``rows = ext.reshape(-1, D)``:
    the shared accumulation of the shifted-row polyphase factorization
    (_poly_decim_fir_stage / resample_stage / xlating_fir_stage), computed over
    rows of width :func:`_row_width` ``R = g·D``. The identity holds for any such
    R — ``Y[j] = Σ_{a=0..m_R} rows_R[j + m_R − a] @ A[a]`` with the band blocks
    of :func:`_band_weights` — and R sets the matmul's shape. Where R > D the
    ``m_R + 1`` shifted views stand side by side and the sum is ONE matmul of
    contraction ``(m_R+1)·R`` (128 taps at D = 4: one ``[512, 256]·[256, 32]``
    complex matmul per lane instead of 33 four-wide matvecs); ``ext`` is
    zero-padded in front to ``m_R`` whole rows and behind to a whole last row.
    One matmul and not a sum of ``m_R + 1``: on the v5e they time the same, and a
    stage that ends in the matmul itself hands XLA:CPU no add to fuse into the
    consumer (with it, ``mag2`` after the stage rounded differently fused and
    alone: tests/test_devchain.py's bit-equality of fused and actor paths).

    HIGHEST precision by default so no TPU bf16 passes sneak
    in. ``precision="bf16"`` (the interior-precision policy, ops/precision.py)
    casts REAL operands to bfloat16 with float32 accumulation — the native MXU
    pass on TPU, the identical quantization on CPU; complex operands (no bf16
    complex exists) fall back to DEFAULT matmul precision, which is the bf16-pass
    path on TPU and a no-op on CPU. ``precision="int8"`` (real weights only —
    the lower hooks guard that) quantizes through :func:`_int8_shifted_matvec`,
    complex streams per re/im plane."""
    from functools import partial as _partial
    D = W.shape[1]
    R = _row_width(D)
    if R != D:
        g = R // D
        mR, nqR = -(-m // g), -(-nq // g)
        front = mR * R - m * D
        rows = jnp.pad(ext, (front, (mR + nqR) * R - front - ext.shape[0])
                       ).reshape(-1, R)
        win = jnp.concatenate([rows[mR - a:mR - a + nqR] for a in range(mR + 1)],
                              axis=1)
        y = _shifted_matvec(win.reshape(-1), _band_weights(W, m, R), 0, nqR,
                            precision)
        return y.reshape((nqR * g,) + W.shape[2:])[:nq]
    rows = ext.reshape(-1, D)
    if precision == "int8" and not jnp.iscomplexobj(W):
        if jnp.iscomplexobj(rows):
            return jax.lax.complex(
                _int8_shifted_matvec(rows.real, W, m, nq),
                _int8_shifted_matvec(rows.imag, W, m, nq))
        return _int8_shifted_matvec(rows, W, m, nq)
    if precision == "bf16" and not (jnp.iscomplexobj(rows)
                                    or jnp.iscomplexobj(W)):
        rows = rows.astype(jnp.bfloat16)
        W = W.astype(jnp.bfloat16)
        mm = _partial(jnp.matmul, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)
    elif precision == "bf16":
        mm = _partial(jnp.matmul, precision=jax.lax.Precision.DEFAULT)
    else:
        mm = _partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    y = mm(rows[m:m + nq], W[0])
    for r in range(1, m + 1):
        y = y + mm(rows[m - r:m - r + nq], W[r])
    return y


def _poly_decim_weights(taps: np.ndarray, D: int, m: int) -> np.ndarray:
    """Arrange ``taps`` as the shifted-row weight matrix ``W[r, s] = taps[r·D − s]``
    (zero where out of range), so ``y[q] = Σ_r rows[q+m−r] · W[r]`` with
    ``rows[j, s] = ext[j·D + s]`` — see :func:`_poly_decim_fir_stage`."""
    nt = len(taps)
    W = np.zeros((m + 1, D), taps.dtype)
    for r in range(m + 1):
        for s in range(D):
            t = r * D - s
            if 0 <= t < nt:
                W[r, s] = taps[t]
    return W


def _poly_decim_fir_stage(taps: np.ndarray, decim: int, fft_len: int,
                          name: str, impl: str,
                          precision: Optional[str] = None) -> Stage:
    """Decimating FIR by the shifted-row polyphase factorization.

    ``y[q] = Σ_t taps[t] · x[q·D − t]``. Decompose ``t = r·D − s``: with
    ``rows[j, s] = ext[j·D + s]`` (a RESHAPE of the input — no copy),
    ``y[q] = Σ_{r=0..m} rows[q+m−r] · W[r]`` where ``W[r, s] = taps[r·D − s]``.
    That D-wide ``W`` is what the CARRY holds (donation-safe and hot-swappable
    exactly like the OS path's frequency-domain ``Hc``); the accumulation itself
    runs over rows of width ``R = g·D`` (:func:`_row_width`), for which the same
    identity holds with band matrices built in the trace from ``W``
    (:func:`_shifted_matvec`, :func:`_band_weights`): one matmul of contraction
    ``(ceil(m/g) + 1)·R``, each row yielding g outputs (why not rows of D, and
    the chip numbers: :func:`_row_width`).
    ``Stage.route[0]`` names the row width that compiled (``"rows128"``).

    ``impl="pallas"`` routes REAL weight matrices through the fused
    FIR→decimate kernel (``pallas_kernels.pallas_poly_fir``): the same
    shifted-row MACs computed inside one kernel at the decimated rate (complex
    frames run two real passes; complex taps keep the matvec path — the kernel
    is real-only). ``precision="bf16"`` carries the weight matrix in bfloat16
    and runs the MACs with bf16 operands / f32 accumulation on either path.
    ``precision="int8"`` (real taps only) runs the shifted MACs as int8×int8
    matmuls with int32 accumulation (:func:`_int8_shifted_matvec`); the Pallas
    kernel is f32/bf16-only, so an int8 build routes the matvec path and the
    carried weights STAY float32 (quantized in-trace) — the carry tree is
    bit-compatible with the f32 stage for brownout/checkpoint conversion.
    """
    D = int(decim)
    nt = len(taps)
    built_real = np.isrealobj(taps)
    m = max(1, -(-(nt - 1) // D))       # history rows so windows never underflow
    H = m * D

    def fn(carry, x):
        W, hist = carry
        ext = jnp.concatenate([hist, x])                 # [H + n]
        if impl == "pallas" and not jnp.iscomplexobj(W) \
                and precision != "int8":
            from .pallas_kernels import pallas_poly_fir
            if jnp.iscomplexobj(x):
                yr = pallas_poly_fir(ext.real.reshape(-1, D), W,
                                     precision=precision)
                yi = pallas_poly_fir(ext.imag.reshape(-1, D), W,
                                     precision=precision)
                y = jax.lax.complex(yr, yi)
            else:
                y = pallas_poly_fir(ext.reshape(-1, D), W,
                                    precision=precision)
        else:
            y = _shifted_matvec(ext, W, m, x.shape[0] // D,
                                precision=precision)
        return (W, ext[ext.shape[0] - H:]), y.astype(x.dtype)

    def _weights(t, complex_stream: bool):
        # a real stream takes .real at the stage boundary (same semantics as the OS
        # path's half-spectrum Hr) — bake that into the carried weights
        teff = t if complex_stream else np.real(t)
        teff = teff.astype(np.complex64 if np.iscomplexobj(teff) else np.float32)
        W = _poly_decim_weights(teff, D, m)
        if precision == "bf16" and not np.iscomplexobj(W):
            import ml_dtypes
            W = W.astype(ml_dtypes.bfloat16)   # carried weights: half the HBM
        return W

    def init_carry(dtype):
        dt = np.dtype(dtype)
        from .xfer import to_device
        return (to_device(_weights(taps, np.issubdtype(dt, np.complexfloating))),
                to_device(np.zeros(H, dtype=dt)))

    def update(carry, taps=None):
        """Runtime tap swap (same count — shapes are static under jit); the carried
        weight matrix is rebuilt with the SAME complex/real treatment init_carry
        applied, keyed on the stream dtype (the carried history's dtype)."""
        if taps is None:
            return carry
        new = np.asarray(taps)
        if len(new) != nt:
            raise ValueError(
                f"tap swap must keep the tap count ({nt}); got {len(new)} — "
                f"rebuild the stage for a different filter length")
        if np.iscomplexobj(new) and built_real:
            raise ValueError(
                "stage was built with real taps; swapping to complex taps "
                "requires rebuilding the stage")
        _w_old, hist = carry
        dev = next(iter(hist.devices())) if isinstance(hist, jax.Array) else None
        complex_stream = np.issubdtype(hist.dtype, np.complexfloating)
        return (_param_to_device(_weights(new, complex_stream), dev), hist)

    def _lower(p: str) -> Optional[Stage]:
        if p not in ("bf16", "int8") or not built_real:
            return None
        return _poly_decim_fir_stage(taps, D, fft_len, name, impl,
                                     precision=p)

    return Stage(fn, init_carry, Fraction(1, D), None, D, name,
                 lti=(taps, D, fft_len, impl), update=update,
                 lower=_lower,
                 compute_dtype=(precision if precision in ("bf16", "int8")
                                else "f32"),
                 route=("pallas" if impl == "pallas" else f"rows{_row_width(D)}",
                        None, precision))


def resample_stage(interp: int, decim: int, taps=None, fft_len: int = 8192,
                   name: str = "resample", impl: str = "poly") -> Stage:
    """Rational I/D resampler as a fused stage — the TPU counterpart of
    ``PolyphaseResamplingFir`` (``futuredsp/polyphase_resampling_fir.rs:41``).

    ``impl="poly"`` (default): true polyphase — phase-grouped stride-D windows built
    from static slices, contracted against the phase-tap matrix in one MXU einsum.
    ``impl="pallas"``: the same factorization computed inside the fused
    polyphase kernel (``pallas_kernels.pallas_poly_fir`` with the 3-D
    phase-tap tensor) — the resampler's inner loop on the autotuned Pallas
    plane; complex frames run two real passes.
    ``impl="stuff"``: the earlier zero-stuff ×I → overlap-save lowpass → ↓D form
    (kept for cross-validation and for complex taps)."""
    from math import gcd

    g = gcd(int(interp), int(decim))
    I, D = int(interp) // g, int(decim) // g
    if taps is None:
        from ..dsp import firdes
        r = max(I, D)
        taps = firdes.kaiser_lowpass(0.5 / r * 0.8, 0.1 / r) * I
    taps = np.asarray(taps)
    assert impl in ("poly", "stuff", "pallas"), impl
    if np.iscomplexobj(taps):
        impl = "stuff"                  # poly path computes a plain taps·x dot; the
                                        # stuffed OS path owns complex-tap semantics

    if impl == "stuff":
        inner = fir_stage(taps, decim=1, fft_len=fft_len, name=f"{name}_fir")
        L = inner.frame_multiple                   # hop of the overlap-save core

        def fn(carry, x):
            n = x.shape[0]
            up = jnp.zeros(n * I, dtype=x.dtype).at[::I].set(x)
            carry, y = inner.fn(carry, up)
            if D > 1:
                y = y[::D]
            return carry, y

        def init_carry(dtype):
            return inner.init_carry(dtype)

        # frame n must satisfy: n·I divisible by the OS hop L and by D
        mult = int(np.lcm(L // np.gcd(I, L), D // np.gcd(I, D)))
        return Stage(fn, init_carry, Fraction(I, D), None, mult, name)

    # Polyphase form (default): output j = Σ_t taps[p_j + I·t] · x[s_j − t] with
    # p_j = (j·D) mod I and s_j = ⌊j·D/I⌋. Outputs grouped by residue r = j mod I
    # share one phase p_r = (r·D) mod I and land on stride-D input offsets
    # s = q·D + c_r. Same shifted-matvec factorization as the poly-decimation FIR
    # (see _poly_decim_fir_stage): per group, y_r[q] = Σ_k phase_r[k]·ext[H + q·D
    # + c_r − k]; decomposing the flat index over the stride-D row matrix gives
    # W[r, a, s] = phase_r[a·D + c_r − s] and
    #   y[:, r] = Σ_{a=0..m} rows[m−a : m−a+nq] @ W[r, a]
    # — m+1 true [n/D, D]·[D, I] MXU matmuls, NO materialized window stack (the
    # previous einsum stacked I per-group window matrices — I·Kmax/D× the input
    # in HBM writes; 48 groups for the audio resampler). Cost stays T/D MACs per
    # input vs the zero-stuffed form's I× inflated FFT frames, with no scatter.
    # D >= 64 (the FM audio resampler's 125) keeps these rows; a smaller D is
    # re-blocked to rows of _row_width(D) inside _shifted_matvec.
    T = len(taps)
    Kmax = -(-T // I)                   # taps per phase
    ftaps = taps.astype(np.float32)
    c_off = [(r_ * D) // I for r_ in range(I)]
    m = max(1, -(-(Kmax - 1) // D))     # history rows so windows never underflow
    #   (also covers the W row range: a ≤ floor((Kmax+D−2)/D) = this m)
    H = m * D
    W = np.zeros((m + 1, D, I), np.float32)       # [row shift, col, group]
    for r_ in range(I):
        phase = ftaps[(r_ * D) % I::I]            # phase_r, length <= Kmax
        for a in range(m + 1):
            for s in range(D):
                k = a * D + c_off[r_] - s
                if 0 <= k < len(phase):
                    W[a, s, r_] = phase[k]

    def fn(carry, x):
        hist = carry
        ext = jnp.concatenate([hist, x])                 # [H + n]
        if impl == "pallas":
            from .pallas_kernels import pallas_poly_fir
            Wj = jnp.asarray(W)
            if jnp.iscomplexobj(x):
                yr = pallas_poly_fir(ext.real.reshape(-1, D), Wj)
                yi = pallas_poly_fir(ext.imag.reshape(-1, D), Wj)
                y = jax.lax.complex(yr, yi)              # [nq, I]
            else:
                y = pallas_poly_fir(ext.reshape(-1, D), Wj)
        else:
            y = _shifted_matvec(ext, jnp.asarray(W), m,
                                x.shape[0] // D)         # [nq, I]
        return ext[ext.shape[0] - H:], y.reshape(-1).astype(x.dtype)

    def init_carry(dtype):
        from .xfer import to_device
        return to_device(np.zeros(H, dtype=np.dtype(dtype)))

    return Stage(fn, init_carry, Fraction(I, D), None, D, name,
                 route=(("pallas", None, None) if impl == "pallas" else None))


def decimate_stage(decim: int) -> Stage:
    def fn(carry, x):
        return carry, x[::decim]

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, decim), None, decim, f"decim{decim}")


def fft_stage(n: int, direction: str = "forward", shift: bool = False,
              normalize: bool = False, window=None,
              impl: Optional[str] = None,
              precision: Optional[str] = None) -> Stage:
    """Batched frame FFT: input frame reshaped [-1, n], transformed on axis 1.
    ``window``: optional name/array applied per frame before a forward FFT.

    ``impl``/``precision`` pin the FFT route and MXU matmul precision PER CALL
    SITE (``mxu_fft.fft(impl=…, precision=…)``): the module ``set_impl`` /
    ``set_precision`` policy binds at trace time and jit caches keep the
    first-bound path, so per-stage pins are how two chains in one process hold
    different routes (the ``ops/mxu_fft.py`` header's promised plumbing).
    ``precision="bf16"`` is also what the interior-precision policy
    (``ops/precision.py``) selects through this stage's ``lower`` hook."""
    if window is not None:
        from ..dsp.windows import get_window
        window = np.asarray(window, dtype=np.float32) if not isinstance(window, str) \
            else get_window(window, n).astype(np.float32)
    fft_prec = "bf16" if precision == "bf16" else None

    def fn(carry, x):
        f = x.reshape(-1, n)
        if direction == "forward":
            if window is not None:
                f = f * jnp.asarray(window)[None, :]
            y = mxu_fft.fft(f, precision=fft_prec, impl=impl)
        else:
            y = mxu_fft.ifft(f, precision=fft_prec, impl=impl) * n
        if normalize:
            y = y / jnp.sqrt(n)
        if shift:
            y = jnp.fft.fftshift(y, axes=1)
        return carry, y.reshape(-1).astype(jnp.complex64)

    def _lower(p: str) -> Optional[Stage]:
        if p != "bf16":
            return None
        return fft_stage(n, direction, shift, normalize, window,
                         impl=impl, precision="bf16")

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), np.complex64, n,
                 f"fft{n}", lower=_lower,
                 compute_dtype="bf16" if precision == "bf16" else "f32",
                 route=(impl, None, precision))


def fir_fft_stage(taps, n_fft: int, name: Optional[str] = None,
                  precision: Optional[str] = None) -> Stage:
    """Fused FIR → windowed-FFT stage (``pallas_kernels.pallas_fir_fft``):
    the filtered stream never round-trips HBM between the filter MAC and the
    transform — the resident ``fir_stage + fft_stage`` chain's whole interior
    edge, collapsed into one kernel.

    Semantically identical (allclose-pinned, tests/test_pallas.py) to
    ``Pipeline([fir_stage(taps), fft_stage(n_fft)])``: frames of ``n_fft``
    samples are filtered causally (history rides the carry) and transformed
    per ``n_fft`` window. REAL taps only, ``2 <= n_taps <= n_fft`` (a tap
    shift must not reach past the transform row directly above — the
    kernel's neighbour-tile precondition). The taps ride the carry, so
    runtime swaps (``update(taps=…)``) reach the kernel with no recompile.
    NOT LTI-mergeable (``lti=None`` — the FFT half is not a filter); the
    ``route`` pin marks the pallas dispatch for the cost-cache marker and
    ``pallas_stage_count``. ``precision="bf16"`` runs MAC + DFT matmuls with
    bf16 operands / f32 accumulation; the ``lower`` hook exposes that to the
    SNR-budgeted interior-precision pass.
    """
    taps = np.asarray(taps)
    nt = len(taps)
    assert np.isrealobj(taps) and 2 <= nt <= int(n_fft), \
        "fir_fft_stage requires real taps with 2 <= n_taps <= n_fft"
    n_fft = int(n_fft)
    name = name or f"fir_fft{n_fft}"

    def fn(carry, x):
        tt, tail = carry
        from .pallas_kernels import pallas_fir_fft
        y = pallas_fir_fft(tail, x, tt, n_fft, precision=precision)
        # frames are >= n_fft >= nt samples, so the new history is the
        # frame's own last nt-1 samples
        return (tt, x[x.shape[0] - (nt - 1):]), y

    def init_carry(dtype):
        from .xfer import to_device
        return (to_device(np.real(taps).astype(np.float32)),
                to_device(np.zeros(nt - 1, dtype=np.dtype(dtype))))

    def update(carry, taps=None):
        """Runtime tap swap (same count; real — the kernel is real-taps-only)."""
        if taps is None:
            return carry
        new = np.asarray(taps)
        if len(new) != nt:
            raise ValueError(
                f"tap swap must keep the tap count ({nt}); got {len(new)} — "
                f"rebuild the stage for a different filter length")
        if np.iscomplexobj(new):
            raise ValueError("fir_fft_stage taps must stay real")
        _tt, tail = carry
        dev = next(iter(tail.devices())) if isinstance(tail, jax.Array) else None
        return (_param_to_device(new.astype(np.float32), dev), tail)

    def _lower(p: str) -> Optional[Stage]:
        if p != "bf16":
            return None
        return fir_fft_stage(taps, n_fft, name=name, precision="bf16")

    return Stage(fn, init_carry, Fraction(1, 1), np.complex64, n_fft, name,
                 update=update, lower=_lower,
                 compute_dtype="bf16" if precision == "bf16" else "f32",
                 route=("pallas", None, precision))


def fftshift_stage(n: int) -> Stage:
    def fn(carry, x):
        return carry, jnp.fft.fftshift(x.reshape(-1, n), axes=1).reshape(-1)

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), None, n, "fftshift")


def mag2_stage() -> Stage:
    def fn(carry, x):
        return carry, (x.real * x.real + x.imag * x.imag).astype(jnp.float32)

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), np.float32, 1, "mag2")


def log10_stage(scale: float = 10.0, floor: float = 1e-20) -> Stage:
    def fn(carry, x):
        return carry, (scale * jnp.log10(jnp.maximum(x, floor))).astype(jnp.float32)

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), np.float32, 1, "log10")


def xlating_fir_stage(taps, phase_inc: float, decim: int,
                      name: str = "xlating") -> Stage:
    """Frequency-translating decimating FIR as ONE fused stage — the TPU form of
    the reference's freq-shift → decimating-FIR front half
    (``examples/fm-receiver/src/main.rs:83-130``; blocks `XlatingFir` role).

    The full-rate rotator is FOLDED into the filter (LTI modulation shift):

        y[q] = Σ_t h[t]·e^{jθ(qD−t)}·x[qD−t]
             = e^{jθDq} · Σ_t (h[t]e^{-jθt}) · x[qD−t]

    so the filter runs with complex taps ``h[t]e^{-jθt}`` via the shifted-row
    polyphase form (:func:`_poly_decim_fir_stage`), and only a residual rotator
    at the DECIMATED rate remains — D× fewer rotations than rotating the input
    (VERDICT r3 weak-item 2: the FM front end's full-rate tuner pass). The taps
    are per listener, so under ``ServeEngine``'s ``vmap`` every lane builds its own
    band matrix from its carried ``W`` (:func:`_band_weights`); for the FM tuner
    (128 taps, D = 4, rows of 128) that is one ``[512, 256]·[256, 32]`` complex
    matmul per lane and frame. ``Stage.route[0]`` names the row width.

    Retune keeps the exact rotator grammar: ``update(phase_inc=θ')`` rebuilds
    the carried weight matrix AND the residual increment in one carry swap (no
    recompile, phase stays continuous); ``update(taps=…)`` swaps the base
    lowpass while preserving the current translation frequency.
    """
    D = int(decim)
    base0 = np.real(np.asarray(taps)).astype(np.float32)
    nt = len(base0)
    m = max(1, -(-(nt - 1) // D))
    H = m * D

    def _weights(base: np.ndarray, theta: float) -> np.ndarray:
        ct = (base * np.exp(-1j * theta * np.arange(nt))).astype(np.complex64)
        return _poly_decim_weights(ct, D, m)

    # The exact translation theta rides the CARRY as a float32 hi/lo pair
    # (double-double split, ~48 significant bits): the carry only holds the
    # float32 decimated increment otherwise, and re-deriving theta from it on
    # a taps-only update() would rebuild the weights with a rounded theta
    # (round-4 advisory). Closure state would alias across carries built from
    # the same Stage (round-5 review) — every other piece of stage state rides
    # the carry, so this does too.
    def _theta_split(theta: float):
        hi = np.float32(theta)
        return hi, np.float32(theta - float(hi))

    def _theta_join(hi, lo) -> float:
        return float(hi) + float(lo)

    def fn(carry, x):
        W, base, ph0, inc_d, th_hi, th_lo, hist = carry
        ext = jnp.concatenate([hist, x])
        nq = x.shape[0] // D
        y = _shifted_matvec(ext, W, m, nq)
        ph = ph0 + inc_d * jnp.arange(nq, dtype=jnp.float32)
        y = y * jnp.exp(1j * ph).astype(y.dtype)
        ph_new = jnp.mod(ph0 + inc_d * nq, 2 * np.pi)
        return (W, base, ph_new, inc_d, th_hi, th_lo,
                ext[ext.shape[0] - H:]), y.astype(x.dtype)

    def init_carry(dtype):
        from .xfer import to_device
        hi, lo = _theta_split(float(phase_inc))
        return (to_device(_weights(base0, float(phase_inc))),
                to_device(base0),
                jnp.zeros((), jnp.float32),
                jnp.asarray(float(phase_inc) * D, jnp.float32),
                jnp.asarray(hi), jnp.asarray(lo),
                to_device(np.zeros(H, dtype=np.dtype(dtype))))

    def update(carry, phase_inc=None, taps=None):
        W, base, ph0, inc_d, th_hi, th_lo, hist = carry
        dev = next(iter(hist.devices())) if isinstance(hist, jax.Array) else None
        nbase = np.asarray(jax.device_get(base), np.float32)
        if taps is not None:
            new = np.asarray(taps)
            if len(new) != nt:
                raise ValueError(f"tap swap must keep the tap count ({nt}); "
                                 f"got {len(new)}")
            if np.iscomplexobj(new):
                raise ValueError("xlating stage taps are the REAL base lowpass; "
                                 "the translation rides phase_inc")
            nbase = new.astype(np.float32)
            base = _param_to_device(nbase, dev)
        if phase_inc is not None:
            theta = float(phase_inc)
            hi, lo = _theta_split(theta)
            def _dev(v):
                return jax.device_put(v, dev) if dev is not None else jnp.asarray(v)
            inc_d = _dev(jnp.asarray(theta * D, jnp.float32))
            th_hi, th_lo = _dev(jnp.asarray(hi)), _dev(jnp.asarray(lo))
        else:
            theta = _theta_join(jax.device_get(th_hi), jax.device_get(th_lo))
        W = _param_to_device(_weights(nbase, theta), dev)
        return (W, base, ph0, inc_d, th_hi, th_lo, hist)

    return Stage(fn, init_carry, Fraction(1, D), None, D, name, update=update,
                 route=(f"rows{_row_width(D)}", None, None))


def rotator_stage(phase_inc: float, name: str = "rotator",
                  impl: str = "xla") -> Stage:
    """Complex rotator with phase carry (futuredsp `Rotator` as a stage).

    The increment rides the CARRY (not the trace), so a runtime retune —
    ``pipeline.update_stage(carries, "rotator", phase_inc=…)`` or the TpuKernel
    ``ctrl`` port — takes effect on the next dispatched frame with phase
    continuity, no recompile: the device-path analog of the fm-receiver's
    ``freq`` handler (``examples/fm-receiver/src/main.rs:83-155``).

    ``impl="pallas"`` routes the phase-ramp multiply through the 2-D lane-tile
    kernel (``pallas_kernels.pallas_rotator`` — the autotuned Pallas plane);
    ``"xla"`` (default) keeps the fused XLA form. Same carry, same retune
    grammar on both routes."""
    assert impl in ("xla", "pallas"), impl

    def fn(carry, x):
        ph0, inc = carry
        n = x.shape[0]
        if impl == "pallas":
            from .pallas_kernels import pallas_rotator
            y = pallas_rotator(x, ph0, inc).astype(x.dtype)
        else:
            ph = ph0 + inc * jnp.arange(n, dtype=jnp.float32)
            y = x * jnp.exp(1j * ph).astype(x.dtype)
        new = jnp.mod(ph0 + inc * n, 2 * np.pi)
        return (new, inc), y

    def init_carry(dtype):
        return (jnp.zeros((), dtype=jnp.float32),
                jnp.asarray(float(phase_inc), dtype=jnp.float32))

    def update(carry, phase_inc=None):
        if phase_inc is None:
            return carry
        ph0, _inc = carry
        new_inc = jnp.asarray(float(phase_inc), dtype=jnp.float32)
        if isinstance(ph0, jax.Array):          # land beside the carry's phase
            new_inc = jax.device_put(new_inc, next(iter(ph0.devices())))
        return (ph0, new_inc)

    return Stage(fn, init_carry, Fraction(1, 1), None, 1, name, update=update,
                 route=(("pallas", None, None) if impl == "pallas" else None))


def quad_demod_stage(gain: float = 1.0, impl: str = "xla") -> Stage:
    """FM discriminator with one-sample carry. ``impl="pallas"`` routes the
    ``angle(x·conj(x₋₁))`` inner loop through the 2-D lane-tile kernel
    (``pallas_kernels.pallas_quad_demod``); the one-sample history carry is
    identical on both routes."""
    assert impl in ("xla", "pallas"), impl

    def fn(carry, x):
        if impl == "pallas":
            from .pallas_kernels import pallas_quad_demod
            y = pallas_quad_demod(carry, x, gain)
            return x[-1], y.astype(jnp.float32)
        prev = jnp.concatenate([carry[None], x[:-1]])
        y = gain * jnp.angle(x * jnp.conj(prev))
        return x[-1], y.astype(jnp.float32)

    def init_carry(dtype):
        # complex host scalars (incl. eager jnp.ones) are host device_puts:
        # ship via the pair shim like every complex upload (ops/xfer.py)
        from .xfer import to_device
        return to_device(np.ones((), dtype=np.dtype(dtype)))

    return Stage(fn, init_carry, Fraction(1, 1), np.float32, 1, "quad_demod",
                 route=(("pallas", None, None) if impl == "pallas" else None))


def apply_stage(f: Callable[[jnp.ndarray], jnp.ndarray], out_dtype=None,
                name: str = "apply") -> Stage:
    """Arbitrary elementwise jax function (1:1)."""

    def fn(carry, x):
        return carry, f(x)

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), out_dtype, 1, name)


def channelizer_stage(n_channels: int, taps=None, name: str = "channelizer",
                      impl: str = "auto",
                      precision: Optional[str] = None) -> Stage:
    """Critically-sampled PFB analysis bank as a stage: frames of k·N complex samples →
    k·N outputs, CHANNEL-INTERLEAVED ([t, N] flattened — feed a StreamDeinterleaver(N)
    to split, or consume interleaved). Carry = the branch-filter history block.

    ``impl="matmul"``: the polyphase branch FIRs as one [N, K] × windows dot per
    output step batched over the frame (MXU work), followed by a batched IFFT
    across branches — the fused-TPU form of `blocks/pfb.PfbChannelizer`.
    ``impl="pallas"``: the fused PFB kernel (``pallas_kernels.pallas_pfb``) —
    polyphase MAC + twiddle-feed IDFT in ONE kernel, so the [t, N] branch bank
    never round-trips HBM between the two passes (the windows stack is ~K× the
    frame in HBM writes on the matmul path). ``"auto"`` picks pallas on the TPU
    backend (trace-time, same convention as ``_pallas_fir_wins``) and the matmul
    path elsewhere. ``precision="bf16"`` carries the branch taps in bfloat16 and
    runs MAC/IDFT with bf16 operands, f32 accumulation (the interior-precision
    policy selects it via this stage's ``lower`` hook).
    """
    assert impl in ("auto", "matmul", "pallas"), impl
    N = n_channels
    if taps is None:
        from ..blocks.pfb import pfb_default_taps
        taps = pfb_default_taps(N)
    taps = np.asarray(taps, dtype=np.float32)
    K = -(-len(taps) // N)
    padded = np.zeros(K * N, dtype=np.float32)
    padded[:len(taps)] = taps
    branch_np = padded.reshape(K, N).T                    # [N, K]
    if precision == "bf16":
        import ml_dtypes
        branch_np = branch_np.astype(ml_dtypes.bfloat16)  # carried taps: half HBM
    fft_prec = "bf16" if precision == "bf16" else None

    def fn(carry, x):
        Hc, hist = carry                                   # hist: [(K-1)·N]
        ext = jnp.concatenate([hist, x])                   # [(t + K-1)·N]
        blocks = ext.reshape(-1, N)[:, ::-1]               # [t+K-1, N] commutated
        t = x.shape[0] // N
        use_pallas = impl == "pallas" or (
            impl == "auto" and jax.default_backend() == "tpu")
        if use_pallas:
            from .pallas_kernels import pallas_pfb
            y = pallas_pfb(blocks, Hc.T, precision=precision)      # [t, N]
        else:
            # windows[s, k, c] = blocks[s + (K-1) - k, c] (branch c, depth k):
            # K static slices + stack instead of a gather (slow on TPU)
            windows = jnp.stack(
                [blocks[(K - 1) - k:(K - 1) - k + t] for k in range(K)],
                axis=1)                                            # [t, K, N]
            prec = (jax.lax.Precision.DEFAULT if precision == "bf16"
                    else jax.lax.Precision.HIGHEST)
            v = jnp.einsum("tkc,ck->tc", windows, Hc, precision=prec)  # [t, N]
            y = mxu_fft.ifft(v, precision=fft_prec) * N    # ifft across branches
        new_hist = ext[ext.shape[0] - (K - 1) * N:]
        return (Hc, new_hist), y.reshape(-1).astype(jnp.complex64)

    def init_carry(dtype):
        from .xfer import to_device
        # a FRESH device copy of the taps per carry: a TpuKernel donates its
        # carry, which deletes the buffer — one array shared by every carry of
        # the stage died with the first warm-up dispatch
        return (jnp.asarray(branch_np),
                to_device(np.zeros((K - 1) * N, dtype=np.dtype(dtype))))

    def _lower(p: str) -> Optional[Stage]:
        if p != "bf16":
            return None
        return channelizer_stage(N, taps, name, impl=impl, precision="bf16")

    return Stage(fn, init_carry, Fraction(1, 1), np.complex64, N, name,
                 lower=_lower,
                 compute_dtype="bf16" if precision == "bf16" else "f32",
                 route=(impl, None, precision))


def lora_downchirp(sf: int, os: int = 1) -> np.ndarray:
    """The conjugate of the base up-chirp of ``2^sf`` chips at ``os`` samples a
    chip (complex128; float32 cannot hold the quadratic phase of SF12)."""
    n = 1 << sf
    u = np.arange(n * os) / os
    return np.exp(-2j * np.pi * (u * u / (2 * n) - u / 2))


def lora_dechirp_dft(blocks: jnp.ndarray, sf: int, os: int = 1, ref=None,
                     precision: Optional[str] = None) -> jnp.ndarray:
    """``[..., os * 2^sf]`` samples of one symbol each → their dechirped
    spectra: the one dechirp-DFT form of the LoRa receivers
    (``lora_demod_stage``, ``models/lora/rx_stages.py``). ``ref``: what the
    samples are multiplied by (default the down-chirp; the gateway passes the
    up-chirp for its down-chirp symbols, and a CFO rotation folded in). The DFT
    is ``mxu_fft.fft``: matmuls on the TPU, ``jnp.fft`` elsewhere."""
    if ref is None:
        ref = jnp.asarray(lora_downchirp(sf, os).astype(np.complex64))
    return mxu_fft.fft(blocks * ref, precision=precision)


def lora_demod_stage(sf: int, name: str = "lora_demod") -> Stage:
    """LoRa dechirp + batched DFT + argmax as a stage: frames of k·2^sf complex chips →
    k int32 symbol values (the `FftDemod` hot loop of the LoRa example, fused)."""
    n = 1 << sf

    def fn(carry, x):
        spec = jnp.abs(lora_dechirp_dft(x.reshape(-1, n), sf))
        return carry, jnp.argmax(spec, axis=1).astype(jnp.int32)

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, n), np.int32, n, name)


def agc_stage(reference: float = 1.0, rate: float = 0.1, block: int = 256,
              max_gain: float = 65536.0) -> Stage:
    """Block-floating AGC: per-sample gain feedback is inherently sequential, so the
    TPU form tracks gain at ``block`` granularity — mean magnitude per block, gain
    evolved by a short ``lax.scan`` over blocks (frame_len/block steps), then applied
    vectorized. Converges like the reference's per-sample loop (`blocks/agc.rs`) with a
    ``block``-sample control delay. Carry = the running gain."""

    def fn(carry, x):
        mags = jnp.abs(x.reshape(-1, block)).mean(axis=1)

        def step(g, m):
            err = reference - m * g
            g = jnp.clip(g + rate * err, 0.0, max_gain)
            return g, g

        g_final, gains = jax.lax.scan(step, carry, mags)
        y = (x.reshape(-1, block) * gains[:, None]).reshape(-1).astype(x.dtype)
        return g_final, y

    def init_carry(dtype):
        return jnp.asarray(1.0, dtype=jnp.float32)

    return Stage(fn, init_carry, Fraction(1, 1), None, block, "agc")


def moving_avg_stage(frame_len: int, decay: float = 0.1) -> Stage:
    """EMA across frames of length ``frame_len`` (spectrum smoothing), carry = the EMA."""

    def fn(carry, x):
        rows = x.reshape(-1, frame_len)

        def step(c, row):
            c = c * (1.0 - decay) + row * decay
            return c, c

        carry, out = jax.lax.scan(step, carry, rows)
        return carry, out.reshape(-1)

    def init_carry(dtype):
        return jnp.zeros(frame_len, dtype=jnp.float32)

    return Stage(fn, init_carry, Fraction(1, 1), np.float32, frame_len, "moving_avg")
