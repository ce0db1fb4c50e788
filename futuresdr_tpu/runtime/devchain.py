"""Device-graph fusion: collapse device-plane chains into ONE dispatch per frame.

The device-plane analog of the native fast chain (``fastchain.py``): where that
module lifts pipes of trivial CPU blocks out of the actor plane into one C++
thread, this one lifts runs of DEVICE blocks out of the per-block dispatch
regime into one jitted XLA program. The reason: every
``TpuStage`` in a flowgraph is its own per-frame jit dispatch, and every stage
boundary materializes the full intermediate frame in HBM — so a k-stage device
chain pays k dispatches and k-1 HBM round trips per frame where the proven
single-``TpuKernel`` path pays one and zero.

At launch the supervisor calls :func:`find_device_chains`; each detected run —

* a linear ``TpuH2D → TpuStage* → TpuD2H`` frame-plane pipeline, or
* adjacent ``TpuKernel`` blocks chained by stream edges (whose intermediate
  hops each cross the host↔device link BOTH ways per frame), or
* a FAN-OUT region ``producer-run → broadcast → N consumer-runs`` in either
  plane (the WLAN ``sync → {demod, channel-est}`` and ``FM → {audio, RDS}``
  shapes): the producer computes once per frame, its boundary value feeds
  every branch INSIDE one multi-output program
  (:class:`~futuresdr_tpu.ops.stages.FanoutPipeline` /
  :class:`~futuresdr_tpu.tpu.TpuFanoutKernel`), so the scarce H2D link is
  paid once instead of N times and 2N+1 per-frame dispatches become 1, or
* a GENERAL DAG region (round 13): NESTED fan-out (a broadcast inside a
  branch, any depth) and FAN-IN — K branch tails joining a frame-plane
  :class:`~futuresdr_tpu.tpu.frames.TpuMergeStage` — including the diamond
  ``producer → broadcast → branches → merge`` closure (WLAN
  ``sync → {demod, chan-est} → decode``, FM ``demod → {audio, RDS} → mux``):
  the whole receiver graph becomes ONE multi-output dispatch per frame
  (:class:`~futuresdr_tpu.ops.stages.DagPipeline` /
  :class:`~futuresdr_tpu.tpu.TpuDagKernel`) whose interior edges never touch
  the host — the merge point's D2H→host→H2D bounce disappears

— is collapsed into one fused :class:`~futuresdr_tpu.tpu.TpuKernel` whose
``Pipeline`` is the concatenation of the member stage lists (composed with
``optimize=False`` and carry-stash fences at member boundaries, so each
member's own numerics are preserved BIT-for-bit — see
:func:`_boundary_stage`). The fused
kernel drives the ORIGINAL boundary ports (the first member's stream input,
the last member's stream output), so buffers, tags and backpressure are the
live flowgraph's own; :func:`run_devchain_task` impersonates every member at
the supervisor protocol level exactly like ``fastchain.run_chain_task`` (init
barrier, Terminate, per-member BlockDone), and a metrics bridge keeps
``metrics()``/REST reporting per ORIGINAL block.

Semantics preserved per block:

* **tags** rebase through the composed rate contract (the same
  ``rebase_frame_tags`` math the members apply hop-by-hop — composition of the
  per-member remaps equals the composed remap);
* **carries** concatenate (each member's stages keep their own carry slots);
* **wire codec** is applied once at the fused edges. For a ``TpuKernel`` run
  with a lossy wire (sc16/sc8) this REMOVES the intermediate hops'
  quantization — strictly higher fidelity, and the reason lossy-wire fused
  output is not bit-identical to the unfused actor path (f32 is).

Refusals (the run stays on the actor path):

* a member whose ``ctrl`` port is wired to a message edge — unless the kernel
  carries the explicit ``devchain_static = True`` opt-in (the
  ``fastchain_static`` convention; see the retune paragraph below for why
  edges refuse while direct ``handle.call`` retunes are serviced);
* members on different ``TpuInstance`` objects (different devices) — for a
  fan-out region this covers every branch (one cross-instance branch declines
  the WHOLE region to per-hop mode: all-or-nothing);
* mismatched wire formats at the fused edges;
* a broadcast whose edges do not ALL open fusable consumer runs (a tap to a
  host sink, a policy-bearing branch member, …) — nested fan-out and
  frame-plane merges FUSE since round 13; what still refuses is a merge
  taking an input from OUTSIDE the region (multi-root, v2), an equal-mode
  merge whose input paths arrive at different rates (rate-contract
  violation), and a region whose sink feeds host blocks that loop back into
  it (a cycle through host edges — the fused block cannot honor the per-hop
  loop's interior queue slack);
* a first-member frame size that is not a multiple of the COMPOSED pipeline's
  frame multiple;
* a per-kernel ``devchain = False`` opt-out, or ``FSDR_NO_DEVCHAIN=1``
  (everything declines — the fallback per-hop path must stand alone, and perf
  probes A/B the two inside one process).

Unlike the native fastchain, ``ctrl`` retunes addressed DIRECTLY to a fused
member (``handle.call(stage, "ctrl", …)``) keep working: each member's stages
occupy a known slice of the composed stage list, so the retune is translated
into carry surgery on the FUSED pipeline between dispatches
(``Pipeline.update_stage`` — same no-recompile contract as ``TpuKernel``'s own
ctrl port), and a ``TpuStage``'s pre-launch queued ctrl (lazy-carry contract)
is applied to the fused carry at compile. Only message-EDGE-wired ctrl ports
refuse to fuse: an edge means another block retunes at stream-synchronized
times, and the fused chain's in-flight batching would shift where the swap
lands.

Known divergences from the unfused actor path (same spirit as fastchain's):

* Calls/Callbacks to ports OTHER than a member's ``ctrl`` answer
  ``Pmt.invalid_value()`` (members have no other handlers today).
* EOS tail handling applies the COMPOSED frame contract once instead of each
  member's contract per hop, so a final partial frame may yield up to one
  frame-multiple fewer tail items than the hop-by-hop path.
* With ``frames_per_dispatch > 1`` the fused kernel adds up to K-1 frames of
  latency while the input trickles (megabatch contract, ``tpu/kernel_block``).
"""

from __future__ import annotations

import asyncio
import os
from fractions import Fraction
from typing import List, Sequence

from ..log import logger
from ..telemetry import journal as _tel_journal
from ..telemetry.spans import recorder as _trace_recorder
from .inbox import (Call, Callback, Initialize, StreamInputDone,
                    StreamOutputDone, Terminate)
from .work_io import WorkIo

__all__ = ["DevChain", "find_device_chains", "run_devchain_task",
           "shed_devchain_bridge", "devchain_enabled"]

log = logger("runtime.devchain")
_trace = _trace_recorder()


def devchain_enabled() -> bool:
    """Env gate, checked per launch (not at import) so perf probes can A/B the
    fused vs per-hop path inside one process. Fault-tolerance degrades fusion
    where (and ONLY where) fused mode would change the semantics
    (docs/robustness.md): a process-default ``isolate`` policy or an armed
    ``work`` / block-addressed ``dispatch:<name>`` campaign falls back to the
    per-hop actor path — the fused chain cannot retire one member or inject
    at per-member work sites. A process-default ``restart`` policy and bare
    ``dispatch`` sites keep fusion ON since the carry-checkpoint/replay PR:
    the fused kernel checkpoints its composed carry, the drive loop restarts
    it in place (bit-correct replay), and its own ``_launch_staged`` polls
    the bare ``dispatch`` site."""
    if os.environ.get("FSDR_NO_DEVCHAIN"):
        return False
    from . import faults as _faults
    from .block import fusion_degraded
    plan = _faults.plan()
    if fusion_degraded(("work",), allow_restart=True) or \
            plan.has_named_site("dispatch") or plan.has_named_site("carry"):
        # block-ADDRESSED dispatch/carry campaigns would silently un-arm in
        # fused mode (the fused kernel polls those sites under ITS name);
        # bare sites stay armed and fusion stays on
        log.info("devchain: failure policy / fault injection armed — "
                 "degrading to per-hop actor mode")
        return False
    return True


class DevChain(list):
    """Fusable device-plane region in topological order. ``kind`` is
    ``"frames"`` (TpuH2D → TpuStage* → TpuD2H) or ``"kernels"`` (adjacent
    TpuKernels). A LINEAR run is the flat member list; a single-level FAN-OUT
    region also carries its topology: ``producer`` (the shared head run) and
    ``branches`` (one member list per consumer run), with the flat list being
    ``producer + branches[0] + … + branches[N-1]`` — the composed-stage /
    metrics / ctrl addressing order everywhere downstream. A general DAG
    region (nested fan-out, fan-IN merges, the diamond closure) instead
    carries ``nodes`` (per member, in flat/topological order: the member
    indices feeding it — a ``TpuMergeStage`` member lists its K ordered
    inputs), ``sinks`` (member indices whose outputs leave the region) and
    ``node_ratios`` (per-member output rate relative to the region input,
    from the validated :class:`~futuresdr_tpu.ops.stages.DagPipeline`)."""

    def __init__(self, members, kind: str, producer=None, branches=None,
                 nodes=None, sinks=None, node_ratios=None):
        super().__init__(members)
        self.kind = kind
        self.producer = producer
        self.branches = branches
        self.nodes = nodes
        self.sinks = sinks
        self.node_ratios = node_ratios

    @property
    def fanout(self) -> bool:
        return self.branches is not None

    @property
    def dag(self) -> bool:
        return self.nodes is not None


class _FwdCtrl:
    """A member-addressed Call/Callback forwarded by an intermediate-member
    watcher into the drive loop's inbox (carry surgery must happen on the
    drive thread, between dispatches)."""

    __slots__ = ("idx", "msg")

    def __init__(self, idx: int, msg):
        self.idx = idx
        self.msg = msg


def _member_ratio(k) -> Fraction:
    pipe = getattr(k, "pipeline", None)
    return pipe.ratio if pipe is not None else Fraction(1, 1)


def find_device_chains(fg) -> List[DevChain]:
    """Maximal fusable device-plane runs in ``fg`` (see module docstring for
    the eligibility/refusal rules)."""
    if not devchain_enabled():
        return []
    from ..ops.stages import Pipeline
    from ..tpu.frames import TpuD2H, TpuH2D, TpuMergeStage, TpuStage
    from ..tpu.kernel_block import TpuKernel

    msg_touched = {id(e.src) for e in fg.message_edges} | \
                  {id(e.dst) for e in fg.message_edges}
    s_out: dict = {}
    s_in: dict = {}
    for e in fg.stream_edges:
        s_out.setdefault(id(e.src), []).append(e)
        s_in.setdefault(id(e.dst), []).append(e)
    i_out: dict = {}
    i_in: dict = {}
    for e in fg.inplace_edges:
        i_out.setdefault(id(e.src), []).append(e)
        i_in.setdefault(id(e.dst), []).append(e)

    def member_ok(k) -> bool:
        """Common per-member gate: opt-out attr, wired-ctrl refusal, and an
        ``isolate``/``isolate_group`` failure policy (retiring ONE member of
        a fused program is not sound — such chains stay on the per-hop actor
        path). ``restart`` members FUSE: the fused kernel checkpoints its
        composed carry and the drive loop restarts it in place, replaying
        bit-correct (``policy_allows_fusion(restartable=True)``) — recovery
        AND fusion, not one or the other."""
        if getattr(k, "devchain", True) is False:
            return False
        if id(k) in msg_touched and not getattr(k, "devchain_static", False):
            # a wired ctrl (or any message port) means live retunes are
            # expected; the fused chain is static — fastchain_static rule
            return False
        from .block import policy_allows_fusion
        if not policy_allows_fusion(k, restartable=True):
            log.debug("devchain refuses %s: isolate failure policy", k)
            return False
        return True

    claimed: set = set()
    chains: List[DevChain] = []

    def _close(members, kind) -> None:
        first = members[0]
        # one wire at both fused edges
        last = members[-1]
        if first.wire.name != last.wire.name:
            log.debug("devchain refuses %s: wire mismatch (%s vs %s)",
                      members, first.wire.name, last.wire.name)
            return
        # one device: instance identity, not equality
        insts = {id(m.inst) for m in members}
        if len(insts) != 1:
            log.debug("devchain refuses %s: mismatched TpuInstances", members)
            return
        stages = [s for m in members
                  if getattr(m, "pipeline", None) is not None
                  for s in m.pipeline.stages]
        in_dtype = first.dtype if kind == "frames" else first.pipeline.in_dtype
        composed = Pipeline(stages, in_dtype, optimize=False)
        if first.frame_size % composed.frame_multiple != 0:
            log.debug("devchain refuses %s: frame %d not a multiple of the "
                      "composed contract %d", members, first.frame_size,
                      composed.frame_multiple)
            return
        if kind == "frames":
            import numpy as np
            if np.dtype(composed.out_dtype) != np.dtype(last.dtype):
                # the unfused TpuD2H casts to ITS dtype at decode; a fused run
                # would emit the pipeline dtype — refuse rather than diverge
                log.debug("devchain refuses %s: D2H dtype %s != composed %s",
                          members, last.dtype, composed.out_dtype)
                return
        claimed.update(id(m) for m in members)
        chains.append(DevChain(members, kind))

    def _close_fanout(producer, branches, kind) -> None:
        """Validate and claim one ``producer → broadcast → N branches``
        region. All-or-nothing: any refusing member already made the caller
        decline, so only the cross-member contracts are checked here."""
        members = list(producer) + [m for br in branches for m in br]
        first = producer[0]
        # one wire at every fused edge: the region's ingress and each
        # branch's egress ("frames": H2D vs each D2H; "kernels": every member
        # carries its own codec edges, so all must agree)
        if kind == "frames":
            wired = [first] + [br[-1] for br in branches]
        else:
            wired = members
        if len({m.wire.name for m in wired}) != 1:
            log.debug("devchain refuses fan-out %s: wire mismatch", members)
            return
        if len({id(m.inst) for m in members}) != 1:
            log.debug("devchain refuses fan-out %s: mismatched TpuInstances",
                      members)
            return
        prod_stages = [s for m in producer
                       if getattr(m, "pipeline", None) is not None
                       for s in m.pipeline.stages]
        in_dtype = first.dtype if kind == "frames" else first.pipeline.in_dtype
        import numpy as np
        fm = 1
        for br in branches:
            br_stages = [s for m in br
                         if getattr(m, "pipeline", None) is not None
                         for s in m.pipeline.stages]
            path = Pipeline(prod_stages + br_stages, in_dtype, optimize=False)
            fm = int(np.lcm(fm, path.frame_multiple))
            if first.frame_size % path.frame_multiple != 0:
                log.debug("devchain refuses fan-out %s: frame %d not a "
                          "multiple of branch contract %d", members,
                          first.frame_size, path.frame_multiple)
                return
            if kind == "frames" and \
                    np.dtype(path.out_dtype) != np.dtype(br[-1].dtype):
                # the unfused TpuD2H casts to ITS dtype at decode (same rule
                # as the linear close)
                log.debug("devchain refuses fan-out %s: D2H dtype %s != "
                          "composed %s", members, br[-1].dtype,
                          path.out_dtype)
                return
        if first.frame_size % fm != 0:
            log.debug("devchain refuses fan-out %s: frame %d not a multiple "
                      "of the composed fan-out contract %d", members,
                      first.frame_size, fm)
            return
        claimed.update(id(m) for m in members)
        chains.append(DevChain(members, kind,
                               producer=list(producer),
                               branches=[list(br) for br in branches]))

    def _host_cycle(members) -> bool:
        """True when a DATA path LEAVES the region (a sink's stream consumer)
        and re-enters it through host blocks — a cycle the fused kernel
        cannot honor (the per-hop pipeline's interior queue slack is what
        kept the loop fed; collapsing the region to one block changes that
        depth). Only backpressure-coupled edges (stream + inplace) count:
        a MESSAGE edge closing the loop (a measurement block retuning a
        ``devchain_static`` member's ``ctrl`` — AGC/AFC feedback) is fine,
        because message inboxes are unbounded and the drive loop applies
        ctrl between dispatches, so no deadlock coupling exists there."""
        member_ids = {id(m) for m in members}
        adj: dict = {}
        for e in (fg.stream_edges + fg.inplace_edges):
            adj.setdefault(id(e.src), []).append(e.dst)
        stack = [d for m in members for d in adj.get(id(m), [])
                 if id(d) not in member_ids]
        seen: set = set()
        while stack:
            b = stack.pop()
            if id(b) in seen:
                continue
            seen.add(id(b))
            for d in adj.get(id(b), []):
                if id(d) in member_ids:
                    return True
                stack.append(d)
        return False

    def _topo(members, node_inputs):
        """Kahn topological order over the region's node graph; None on a
        cycle (decline — inplace graphs should be acyclic, but a hand-wired
        cycle must not wedge the finder)."""
        n = len(members)
        indeg = [0] * n
        cons: List[list] = [[] for _ in range(n)]
        for i, ins in enumerate(node_inputs):
            for j in ins:
                indeg[i] += 1
                cons[j].append(i)
        order = [i for i in range(n) if indeg[i] == 0]
        qi = 0
        while qi < len(order):
            for c in cons[order[qi]]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    order.append(c)
            qi += 1
        return order if len(order) == n else None

    def _close_dag(members, node_inputs, kind) -> None:
        """Validate and claim one GENERAL DAG region (nested fan-out, fan-in
        merges, the diamond closure) — all-or-nothing, exactly like the
        linear/fan-out closers: any cross-member contract violation declines
        the whole region to the per-hop actor path."""
        from ..ops.stages import DagPipeline
        first = members[0]
        if len({id(m.inst) for m in members}) != 1:
            log.debug("devchain refuses DAG %s: mismatched TpuInstances",
                      members)
            return
        in_dtype = first.dtype if kind == "frames" else first.pipeline.in_dtype
        try:
            # _member_fused_stages is THE member→stage-list mapping (shared
            # with the builder, so the finder can never validate a different
            # stage list than _build_fused_dag compiles)
            dag = DagPipeline(
                [(_member_fused_stages(m), node_inputs[i])
                 for i, m in enumerate(members)], in_dtype, optimize=False)
        except ValueError as e:
            # merge rate-contract violations, malformed merges, … — the
            # region declines honestly rather than fusing something whose
            # composed contract the actor path does not have
            log.debug("devchain refuses DAG %s: %s", members, e)
            return
        # ONE definition of "sink" everywhere: the validated pipeline's
        # (consumer-free nodes) — the wire check, the dtype check and the
        # claimed chain all read dag.sinks
        if kind == "frames":
            wired = [first] + [members[i] for i in dag.sinks]
        else:
            wired = members
        if len({m.wire.name for m in wired}) != 1:
            log.debug("devchain refuses DAG %s: wire mismatch", members)
            return
        if first.frame_size % dag.frame_multiple != 0:
            log.debug("devchain refuses DAG %s: frame %d not a multiple of "
                      "the composed contract %d", members, first.frame_size,
                      dag.frame_multiple)
            return
        if kind == "frames":
            import numpy as np
            for j, i in enumerate(dag.sinks):
                if np.dtype(dag.out_dtypes[j]) != np.dtype(members[i].dtype):
                    # the unfused TpuD2H casts to ITS dtype at decode (same
                    # rule as the linear/fan-out closers)
                    log.debug("devchain refuses DAG %s: D2H dtype %s != "
                              "composed %s", members, members[i].dtype,
                              dag.out_dtypes[j])
                    return
        claimed.update(id(m) for m in members)
        chains.append(DevChain(members, kind, nodes=list(node_inputs),
                               sinks=list(dag.sinks),
                               node_ratios=list(dag.node_ratios)))

    def _classify(node_inputs) -> str:
        """``linear`` / ``fanout`` (single broadcast level, no merge — the
        PR 6 shape) / ``dag`` (everything else the new path fuses)."""
        if any(len(ins) > 1 for ins in node_inputs):
            return "dag"
        cons = [0] * len(node_inputs)
        for ins in node_inputs:
            for j in ins:
                cons[j] += 1
        multi = [i for i, c in enumerate(cons) if c > 1]
        if not multi:
            return "linear"
        return "fanout" if len(multi) == 1 else "dag"

    def _split_fanout(members, node_inputs):
        """Decompose a single-broadcast tree into (producer, branches) — the
        PR 6 representation (flat order producer + branches concatenated)."""
        n = len(members)
        cons: List[list] = [[] for _ in range(n)]
        for i, ins in enumerate(node_inputs):
            for j in ins:
                cons[j].append(i)
        b = next(i for i in range(n) if len(cons[i]) > 1)
        producer, cur = [], 0
        while True:
            producer.append(members[cur])
            if cur == b:
                break
            cur = cons[cur][0]
        branches = []
        for head in cons[b]:
            br, cur = [], head
            while True:
                br.append(members[cur])
                if not cons[cur]:
                    break
                cur = cons[cur][0]
            branches.append(br)
        return producer, branches

    def _chain_order(members, node_inputs):
        """Flat member order of a linear region (root → sink)."""
        n = len(members)
        nxt = {}
        for i, ins in enumerate(node_inputs):
            for j in ins:
                nxt[j] = i
        out, cur = [members[0]], 0
        while cur in nxt:
            cur = nxt[cur]
            out.append(members[cur])
        return out

    def _close_region(members, node_inputs, kind) -> None:
        shape = _classify(node_inputs)
        if _host_cycle(members):
            log.debug("devchain refuses %s region %s: cycle through host "
                      "edges", shape, members)
            return
        if shape == "linear":
            if len(members) >= 2:
                _close(_chain_order(members, node_inputs), kind)
        elif shape == "fanout":
            producer, branches = _split_fanout(members, node_inputs)
            _close_fanout(producer, branches, kind)
        else:
            _close_dag(members, node_inputs, kind)

    kernels = [b.kernel for b in fg._blocks if b is not None]

    # ---- frame-plane regions: the general DAG rooted at a TpuH2D ------------
    # (linear runs, single- and NESTED fan-out, fan-IN through TpuMergeStage,
    # and the diamond broadcast→merge closure — one grower, all-or-nothing)
    def _grow_frame_dag(root):
        """Forward closure of ``root`` over inplace edges; returns
        ``(members, node_inputs)`` in topological order, or None when any
        reachable consumer refuses (the whole region declines)."""
        members, idx = [root], {id(root): 0}
        qi = 0
        while qi < len(members):
            cur = members[qi]
            qi += 1
            if type(cur) is TpuD2H:
                continue                 # sinks end the plane
            outs = i_out.get(id(cur), [])
            if not outs:
                log.debug("devchain refuses region at %s: dangling device "
                          "node %s", root, cur)
                return None
            for e in outs:
                nxt = e.dst
                if id(nxt) in idx:
                    continue             # another edge into a known member
                if type(nxt) not in (TpuStage, TpuMergeStage, TpuD2H) \
                        or id(nxt) in claimed or not member_ok(nxt):
                    log.debug("devchain refuses region at %s: consumer %s",
                              root, nxt)
                    return None
                if type(nxt) in (TpuStage, TpuMergeStage) \
                        and nxt._carry is not None:
                    # mid-stream state from a previous run: the actor path
                    # resumes it, a fused fresh carry would not
                    log.debug("devchain refuses region at %s: %s carries "
                              "mid-stream state", root, nxt)
                    return None
                if type(nxt) is TpuD2H and (
                        i_out.get(id(nxt)) or not s_out.get(id(nxt))):
                    log.debug("devchain refuses region at %s: D2H %s must "
                              "exit to the stream plane", root, nxt)
                    return None
                idx[id(nxt)] = len(members)
                members.append(nxt)
        node_inputs: List[list] = []
        for m in members:
            if m is root:
                node_inputs.append([])
                continue
            ins = i_in.get(id(m), [])
            if type(m) is TpuMergeStage:
                by_port = {}
                for e in ins:
                    if e.dst_port in by_port:
                        log.debug("devchain refuses region at %s: merge "
                                  "port %s double-wired", root, e.dst_port)
                        return None
                    by_port[e.dst_port] = e.src
                srcs = []
                for i in range(m.merge.k):
                    s = by_port.get(f"in{i}")
                    if s is None:
                        log.debug("devchain refuses region at %s: merge "
                                  "input in%d unwired", root, i)
                        return None
                    srcs.append(s)
            else:
                if len(ins) != 1:
                    log.debug("devchain refuses region at %s: %s has %d "
                              "inputs", root, m, len(ins))
                    return None
                srcs = [ins[0].src]
            if any(id(s) not in idx for s in srcs):
                # an input from OUTSIDE the closure: a second root feeding
                # the merge (multi-root regions decline, v1)
                log.debug("devchain refuses region at %s: %s takes an "
                          "input from outside the region", root, m)
                return None
            node_inputs.append([idx[id(s)] for s in srcs])
        order = _topo(members, node_inputs)
        if order is None:
            log.debug("devchain refuses region at %s: cyclic inplace graph",
                      root)
            return None
        remap = {old: new for new, old in enumerate(order)}
        members = [members[i] for i in order]
        node_inputs = [[remap[j] for j in node_inputs[i]] for i in order]
        return members, node_inputs

    for k in kernels:
        if type(k) is not TpuH2D or id(k) in claimed or not member_ok(k):
            continue
        if len(s_in.get(id(k), [])) != 1 or not i_out.get(id(k)):
            continue                     # unwired H2D
        region = _grow_frame_dag(k)
        if region is not None and len(region[0]) >= 2:
            _close_region(region[0], region[1], "frames")

    # ---- TpuKernel regions over stream edges (out-trees: linear runs and
    # fan-outs at ANY depth; stream ports are single-writer, so fan-IN is
    # inexpressible on this plane — it rides the frame plane's merge block) --
    def _kernel_ok(k) -> bool:
        # exact-type check: a TpuFanoutKernel/TpuDagKernel (or any subclass)
        # manages its own sinks and never joins a chain
        return (type(k) is TpuKernel and id(k) not in claimed and member_ok(k)
                and not i_out.get(id(k)) and not i_in.get(id(k)))

    def _follows(a, b) -> bool:
        """``b`` can extend a region whose member ``a`` feeds it."""
        return (_kernel_ok(b) and len(s_in.get(id(b), [])) == 1
                and id(b.inst) == id(a.inst) and b.wire.name == a.wire.name)

    def _will_extend(src, k) -> bool:
        """``src``'s region will actually absorb its consumer ``k``: a single
        edge extends when the consumer follows; a BROADCAST extends only when
        EVERY consumer follows (mixed broadcasts truncate — see the grower)."""
        outs = s_out.get(id(src), [])
        if len(outs) == 1:
            return _follows(src, k)
        return all(_follows(src, e.dst) for e in outs)

    def _is_head(k) -> bool:
        """A region head: no fusable upstream will absorb k. Mirrors the
        grower exactly: under a MIXED broadcast (one consumer not fusable)
        the producer's region truncates at the broadcast owner, so each
        fusable branch head IS a head and fuses its own run — the round-11
        behavior (the prefix and every clean branch still fuse linearly)."""
        ups = s_in.get(id(k), [])
        return not (len(ups) == 1 and _kernel_ok(ups[0].src)
                    and _will_extend(ups[0].src, k))

    def _grow_kernel_tree(root):
        """Forward closure of ``root`` over stream edges: a branch ENDS at a
        non-fusable single consumer (the member becomes a sink feeding it),
        and a BROADCAST with any non-fusable consumer TRUNCATES the region at
        the broadcast owner — its output port is driven by the fused kernel
        and the port group still broadcasts to every (unfused) consumer,
        exactly as a round-8 linear chain ending on a broadcasting port did;
        the fusable branches fuse as their own regions (``_is_head``). BFS
        order is topological for an out-tree."""
        members, idx = [root], {id(root): 0}
        node_inputs: List[list] = [[]]
        qi = 0
        while qi < len(members):
            cur = members[qi]
            qi += 1
            outs = s_out.get(id(cur), [])
            if len(outs) == 1:
                nxt = outs[0].dst
                if not _follows(cur, nxt) or id(nxt) in idx:
                    continue             # branch ends: cur is a region sink
                idx[id(nxt)] = len(members)
                members.append(nxt)
                node_inputs.append([idx[id(cur)]])
            elif len(outs) > 1:
                if any(not _follows(cur, e.dst) or id(e.dst) in idx
                       for e in outs):
                    log.debug("devchain region at %s truncates at %s: mixed "
                              "broadcast (a consumer is not fusable)",
                              root, cur)
                    continue             # cur is a region sink; port-group
                    #                      broadcast serves the consumers
                for e in outs:
                    nxt = e.dst
                    idx[id(nxt)] = len(members)
                    members.append(nxt)
                    node_inputs.append([idx[id(cur)]])
        return members, node_inputs

    for k in kernels:
        if not _kernel_ok(k) or not _is_head(k):
            continue
        region = _grow_kernel_tree(k)
        if region is not None and len(region[0]) >= 2:
            _close_region(region[0], region[1], "kernels")
    return chains


# ---------------------------------------------------------------------------
# fused kernel construction + metrics bridge
# ---------------------------------------------------------------------------

def _boundary_stage(n_items: int, dtype):
    """Identity stage fencing a member boundary: the boundary frame is stashed
    into the CARRY (``return x, x``), which makes it a program OUTPUT root —
    XLA then materializes exactly the value the standalone member program
    would have produced, so each member's segment of the fused program
    compiles to the member's own numerics bit-for-bit (the fused-vs-actor
    bit-equality contract; a bare ``lax.optimization_barrier`` proved
    insufficient — consumer-side fusion still reassociated the rounding).
    The frame never leaves the device or the program — the cost is one
    donated HBM buffer write per boundary per dispatch, not a host hop or an
    extra dispatch."""
    import numpy as np

    from ..ops.stages import Stage

    def fn(carry, x):
        return x, x

    def init_carry(_dt):
        from ..ops.xfer import to_device
        # to_device, not eager jnp.zeros: complex host constants ride the
        # pair shim like every other complex upload (ops/xfer.py)
        return to_device(np.zeros(n_items, dtype=dtype))

    return Stage(fn, init_carry, name="devchain_boundary")


def _resolve_k_batch(first, chain_kind: str, sig_pipe_or_stages, in_dtype):
    """The megabatch K a fused chain launches with: an explicit per-kernel or
    config K wins; with the knob unset (0 = auto), a chain that
    ``autotune_streamed`` already tuned launches with ITS cached pick (the
    streamed-pick cache, keys ignore devchain boundary fences — fan-out
    shapes key on their branch structure). Shared by the linear and fan-out
    builders; see the linear builder's comment for the latency contract."""
    if chain_kind == "frames":
        k_batch = None                   # config default (frame plane has no knob)
    else:
        k_batch = first.k_batch
    if k_batch is None or (k_batch == 1 and not first._k_explicit):
        from ..config import config
        if int(config().tpu_frames_per_dispatch) == 0:
            from ..tpu.autotune import cached_frames_per_dispatch
            k = cached_frames_per_dispatch(sig_pipe_or_stages, in_dtype,
                                           first.inst.platform)
            if k and k > 1:
                log.info("devchain: frames_per_dispatch=%d from cached "
                         "autotune_streamed pick", k)
                k_batch = k
    return k_batch


def _members_pinned_depth(members) -> bool:
    """Did ANY member pin its in-flight depth explicitly (per-kernel
    ``frames_in_flight`` / ``max_inflight`` argument)? The fused kernel's
    credit controller then pins too — fusion must not un-pin a budget the
    user fixed (``TpuKernel._adopt_credit_mode`` additionally honors a
    config ``tpu_inflight`` pin)."""
    return any(getattr(m, "_depth_explicit", False) for m in members)


def _build_fused(chain: DevChain):
    """One TpuKernel over the members' concatenated stage lists, driving the
    chain's ORIGINAL boundary ports (the live, already-materialized buffers).
    Fan-out regions route to :func:`_build_fused_fanout` (one
    ``TpuFanoutKernel`` with a multi-output program)."""
    import numpy as np

    from ..ops.stages import Pipeline
    from ..tpu.kernel_block import TpuKernel

    if chain.dag:
        return _build_fused_dag(chain)
    if chain.fanout:
        return _build_fused_fanout(chain)

    members = list(chain)
    first, last = members[0], members[-1]
    in_dtype = first.dtype if chain.kind == "frames" \
        else first.pipeline.in_dtype
    pipes = [m.pipeline for m in members
             if getattr(m, "pipeline", None) is not None]
    frame = first.frame_size
    # "frames" runs also fence the wire codec off the member stages: the
    # unfused TpuH2D/TpuD2H run decode/encode as STANDALONE programs, so the
    # fused segments must match those numerics too ("kernels" members fuse
    # their own codec edges in the unfused path already — no edge fence there)
    fence_edges = chain.kind == "frames"
    stages: list = []
    slices: list = []        # per MEMBER: (start, stop) into the composed list
    cum = Fraction(1, 1)
    dt = np.dtype(in_dtype)
    seen_pipes = 0
    if fence_edges and pipes:
        stages.append(_boundary_stage(frame, dt))
    for m in members:
        p = getattr(m, "pipeline", None)
        if p is None:
            slices.append((len(stages), len(stages)))
            continue
        if seen_pipes > 0:
            q = Fraction(frame) * cum
            assert q.denominator == 1, (frame, cum)   # finder checked the lcm
            stages.append(_boundary_stage(int(q), dt))
        slices.append((len(stages), len(stages) + len(p.stages)))
        stages.extend(p.stages)
        cum *= p.ratio
        dt = np.dtype(p.out_dtype)
        seen_pipes += 1
    if fence_edges and pipes:
        q = Fraction(frame) * cum
        assert q.denominator == 1, (frame, cum)
        stages.append(_boundary_stage(int(q), dt))
    if chain.kind == "frames":
        in_dtype = first.dtype
        depth = first.max_inflight
    else:
        in_dtype = first.pipeline.in_dtype
        depth = first.depth
    # ROADMAP follow-up (PR 4): with the config knob unset (the default K=1),
    # a chain that `autotune_streamed` already tuned in this process launches
    # with ITS measured megabatch K — the sweep's verdict carries over to the
    # fused dispatch without re-measuring (the cache key ignores the boundary
    # fences, so the composed stage list maps back to the tuned chain). This
    # inherits megabatching's latency contract: partial K-groups flush only
    # at EOS, so a trickle/bursty source buffers up to K-1 frames — set
    # tpu_frames_per_dispatch=1 explicitly to pin dispatch-per-frame for
    # latency-critical chains (an explicit config always wins over the cache).
    k_batch = _resolve_k_batch(first, chain.kind, stages, in_dtype)
    # optimize=False: each member's internal numerics stay stage-for-stage
    # identical to the unfused run (cross-member LTI merging would convolve
    # taps and break the bit-equality contract); XLA still fuses elementwise
    # work across the boundaries inside the single program
    composed = Pipeline(stages, in_dtype, optimize=False)
    fused = TpuKernel((), in_dtype, frame_size=first.frame_size,
                      inst=first.inst, frames_in_flight=depth,
                      wire=first.wire, frames_per_dispatch=k_batch,
                      _pipeline=composed)
    assert fused.frame_size == first.frame_size, \
        (fused.frame_size, first.frame_size)    # finder checked the multiple
    # credit adaptivity follows the MEMBERS' explicitness (the builder's own
    # frames_in_flight argument would otherwise pin the fused budget)
    fused._adopt_credit_mode(not _members_pinned_depth(members))
    # steal the boundary ports: the fused kernel works the chain's own buffers
    fused._stream_inputs = [first.input]
    fused._stream_outputs = [last.output]
    fused.input = first.input
    fused.output = last.output
    fused.meta.instance_name = \
        f"devchain[{type(first).__name__}…x{len(members)}]"
    fused._dc_slices = slices    # per-member stage ranges for ctrl translation
    return fused


def _build_fused_fanout(chain: DevChain):
    """One :class:`~futuresdr_tpu.tpu.TpuFanoutKernel` over the region's
    composed fan-out DAG, driving the producer's ORIGINAL input port and each
    branch tail's ORIGINAL output port.

    Fences (see :func:`_boundary_stage`): every member boundary is fenced
    exactly as in the linear builder, and the PRODUCER → BRANCHES boundary
    always carries one — it pins the multiply-consumed broadcast value to the
    standalone producer's numerics (every branch then reads the SAME
    materialized frame the actor path would have broadcast), and doubles as
    the donation story: the boundary value is a carry-resident program output
    root, never a donated argument
    (:class:`~futuresdr_tpu.ops.stages.FanoutPipeline`)."""
    import numpy as np

    from ..ops.stages import FanoutPipeline
    from ..tpu.kernel_block import TpuFanoutKernel

    producer, branches = chain.producer, chain.branches
    first = producer[0]
    fence_edges = chain.kind == "frames"
    frame = first.frame_size
    in_dtype = first.dtype if chain.kind == "frames" \
        else first.pipeline.in_dtype
    slices: list = []        # per MEMBER (flat chain order): composed range

    def walk(seg_members, cum0, dt0, base, lead, trail):
        """Compose one segment's stage list with member fences; returns
        ``(stages, cum, dt)`` and appends the segment's member slices at flat
        offset ``base``."""
        stages: list = []
        cum, dt, seen = cum0, np.dtype(dt0), 0

        def fence():
            q = Fraction(frame) * cum
            assert q.denominator == 1, (frame, cum)  # finder checked the lcm
            stages.append(_boundary_stage(int(q), dt))

        if lead:
            fence()
        for m in seg_members:
            p = getattr(m, "pipeline", None)
            if p is None:
                slices.append((base + len(stages), base + len(stages)))
                continue
            if seen > 0:
                fence()
            slices.append((base + len(stages),
                           base + len(stages) + len(p.stages)))
            stages.extend(p.stages)
            cum *= p.ratio
            dt = np.dtype(p.out_dtype)
            seen += 1
        if trail and (seen > 0 or not lead):
            fence()
        return stages, cum, dt

    # producer: edge fence on the frame plane, and ALWAYS a boundary fence at
    # the end (the lead fence doubles as it for a stage-less H2D producer)
    p_stages, cum_p, dt_p = walk(producer, Fraction(1, 1), in_dtype, 0,
                                 lead=fence_edges, trail=True)
    base = len(p_stages)
    branch_lists = []
    for br in branches:
        has_pipes = any(getattr(m, "pipeline", None) is not None for m in br)
        b_stages, _, _ = walk(br, cum_p, dt_p, base, lead=False,
                              trail=fence_edges and has_pipes)
        branch_lists.append(b_stages)
        base += len(b_stages)
    # optimize=False: the bit-equality contract, exactly as the linear builder
    fanout = FanoutPipeline(p_stages, branch_lists, in_dtype, optimize=False)
    depth = first.max_inflight if chain.kind == "frames" else first.depth
    k_batch = _resolve_k_batch(first, chain.kind, fanout, in_dtype)
    fused = TpuFanoutKernel(fanout, frame_size=frame, inst=first.inst,
                            frames_in_flight=depth, wire=first.wire,
                            frames_per_dispatch=k_batch)
    assert fused.frame_size == frame, (fused.frame_size, frame)
    fused._adopt_credit_mode(not _members_pinned_depth(list(chain)))
    # steal the boundary ports: the region's own input and each branch tail's
    # own output — buffers, tags and backpressure stay the live flowgraph's
    tails = [br[-1] for br in branches]
    fused._stream_inputs = [first.input]
    fused.input = first.input
    fused._stream_outputs = [t.output for t in tails]
    fused.outputs = [t.output for t in tails]
    fused.output = fused.outputs[0]
    fused.meta.instance_name = (
        f"devchain[{type(first).__name__}…x{len(chain)}"
        f"⇉{len(branches)}]")
    fused._dc_slices = slices
    return fused


def _member_fused_stages(m) -> list:
    """THE member → fused-stage-list mapping, shared by the finder's DAG
    validation and the builder: ``[merge] + post`` for a TpuMergeStage, the
    pipeline stages for TpuStage/TpuKernel, [] for the stage-less H2D/D2H
    endpoints."""
    from ..tpu.frames import TpuMergeStage
    if type(m) is TpuMergeStage:
        return [m.merge] + list(m.post)
    p = getattr(m, "pipeline", None)
    return list(p.stages) if p is not None else []


def _build_fused_dag(chain: DevChain):
    """One :class:`~futuresdr_tpu.tpu.TpuDagKernel` over the region's general
    DAG, driving the root's ORIGINAL input port and each SINK's ORIGINAL
    output port.

    Fences (:func:`_boundary_stage`): every INTERIOR member gets a trailing
    carry-stash fence — which uniformly covers all three fence roles of the
    linear/fan-out builders: the frame-plane edge fences (the stage-less
    H2D/D2H endpoints contribute fence-only nodes), the member-boundary
    fences that pin each member segment to its standalone numerics, and the
    multiply-consumed-value fences (a broadcast point is always a member
    boundary, so its value is a program OUTPUT root that donation can never
    alias — the PR 6 contract, generalized). MERGE inputs are member
    boundaries too, so each joined value is pinned before the merge reads
    it — the fused diamond reads bit-identical branch values to the per-hop
    broadcast run. KERNELS-plane SINKS carry no trailing fence, mirroring
    the linear/fan-out builders' no-edge-fence rule there: the unfused
    TpuKernel lets XLA fuse its final stage into the wire encode, and the
    fused sink must compile to the same numerics."""
    from ..ops.stages import DagPipeline
    from ..tpu.kernel_block import TpuDagKernel

    members = list(chain)
    first = members[0]
    frame = first.frame_size
    in_dtype = first.dtype if chain.kind == "frames" \
        else first.pipeline.in_dtype
    # a no-fence validation pass resolves every node's output rate/dtype —
    # the fence sizes (the finder already built this once; rebuilding keeps
    # the builder usable standalone)
    plain = DagPipeline([(_member_fused_stages(m), chain.nodes[i])
                         for i, m in enumerate(members)], in_dtype,
                        optimize=False)
    slices: list = []
    nodes: list = []
    off = 0
    import numpy as np
    sink_set = set(plain.sinks)
    for i, m in enumerate(members):
        sl = _member_fused_stages(m)
        stages = list(sl)
        if not (chain.kind == "kernels" and i in sink_set):
            # trailing boundary fence (docstring); kernels-plane sinks skip
            # it so the final stage fuses into the wire encode exactly as
            # the member's own standalone program would
            q = Fraction(frame) * plain.node_ratios[i]
            assert q.denominator == 1, (frame, plain.node_ratios[i])
            stages.append(_boundary_stage(int(q),
                                          np.dtype(plain.node_dtypes[i])))
        slices.append((off, off + len(sl)))      # member-local ctrl range
        off += len(stages)
        nodes.append((stages, chain.nodes[i]))
    # optimize=False: the bit-equality contract, exactly as the linear builder
    dag = DagPipeline(nodes, in_dtype, optimize=False)
    depth = first.max_inflight if chain.kind == "frames" else first.depth
    k_batch = _resolve_k_batch(first, chain.kind, dag, in_dtype)
    fused = TpuDagKernel(dag, frame_size=frame, inst=first.inst,
                         frames_in_flight=depth, wire=first.wire,
                         frames_per_dispatch=k_batch)
    assert fused.frame_size == frame, (fused.frame_size, frame)
    fused._adopt_credit_mode(not _members_pinned_depth(members))
    # steal the boundary ports: the region's own input and each sink's own
    # output — buffers, tags and backpressure stay the live flowgraph's
    tails = [members[i] for i in chain.sinks]
    fused._stream_inputs = [first.input]
    fused.input = first.input
    fused._stream_outputs = [t.output for t in tails]
    fused.outputs = [t.output for t in tails]
    fused.output = fused.outputs[0]
    fused.meta.instance_name = (
        f"devchain[{type(first).__name__}…x{len(members)}"
        f"⋈{len(tails)}]")
    fused._dc_slices = slices
    return fused


def _port_name(kernel, port):
    """Resolve a Call/Callback port id to a handler NAME the way
    ``Kernel.call_handler`` does (PortId / int index / str)."""
    from ..types import PortId
    pid = port.id if isinstance(port, PortId) else port
    if isinstance(pid, int):
        names = kernel.message_input_names()
        return names[pid] if 0 <= pid < len(names) else None
    return pid


def _apply_stage_update(fused, idx: int, stage, params: dict) -> None:
    """Translate a MEMBER-local stage address (name or index) into the fused
    pipeline's composed index and apply the carry surgery through the
    kernel's replay-exact retune path (``TpuKernel.apply_retune`` — logged
    for checkpoint-replay re-application, deferred past an active replay
    window). Raises on a bad address — callers answer
    ``Pmt.invalid_value()`` exactly like the member's own handler would."""
    start, stop = fused._dc_slices[idx]
    if isinstance(stage, str):
        hits = [j for j in range(start, stop)
                if fused.pipeline.stages[j].name == stage]
        if not hits:
            raise KeyError(f"no stage named {stage!r} in fused member {idx}")
        if len(hits) > 1:
            raise KeyError(f"stage name {stage!r} is ambiguous")
        j = hits[0]
    else:
        j = start + int(stage)
        if not start <= j < stop:
            raise KeyError(f"stage index {stage} out of member range")
    fused.apply_retune(j, params)


def _apply_ctrl(fused, member_kernels, idx: int, port, p):
    """Service a ``ctrl`` retune addressed to fused member ``idx`` (the
    TpuKernel/TpuStage retune contract survives fusion — frames already in
    flight keep the old parameters, later dispatches see the new ones).
    Non-ctrl ports answer invalid, as the member itself would for an unknown
    handler."""
    from ..tpu.frames import parse_ctrl
    from ..types import Pmt
    k = member_kernels[idx]
    if _port_name(k, port) != "ctrl" or "ctrl" not in k.message_input_names():
        return Pmt.invalid_value()
    try:
        stage, params = parse_ctrl(p)
        # apply_retune handles retune-in-replay itself (docs/robustness.md
        # replay-aware retunes): surgery landing inside an active replay
        # window is deferred to the post-window boundary with a structured
        # warning, and every applied retune is logged so a later checkpoint
        # replay re-applies it at exactly its original frame
        _apply_stage_update(fused, idx, stage, params)
    except Exception as e:                             # noqa: BLE001
        log.warning("devchain ctrl rejected: %r", e)
        return Pmt.invalid_value()
    return Pmt.ok()


def shed_devchain_bridge(kernel) -> None:
    """Restore a kernel's pre-fusion ``extra_metrics`` if a fused devchain run's
    bridge is installed (the exact counterpart of
    ``fastchain.shed_metrics_bridge`` — the supervisor calls both for every
    actor-path block at launch)."""
    if not hasattr(kernel, "_dc_base_extra"):
        return
    base = kernel._dc_base_extra
    if base is None:
        try:
            del kernel.extra_metrics
        except AttributeError:
            pass
    else:
        kernel.extra_metrics = base
    del kernel._dc_base_extra


def _chain_rates(chain: DevChain) -> list:
    """Per member (flat chain order): ``(kernel, cumulative in-rate,
    cumulative out-rate, branch)`` relative to the fused region's input.
    ``branch`` is None for linear chains and producer members, else the
    member's branch index — fan-out branch members restart the cumulative
    walk from the producer's boundary rate. DAG regions read the validated
    node rates (``chain.node_ratios``); a merge member's in-rate is the
    TUPLE of its input-port rates, and ``branch`` becomes the member's SINK
    index when exactly one sink consumes it (shared producers report
    None)."""
    if chain.dag:
        n = len(chain)
        cons: list = [[] for _ in range(n)]
        for i, ins in enumerate(chain.nodes):
            for j in ins:
                cons[j].append(i)
        # per member: the set of sinks its value reaches (for attribution)
        reach = [set() for _ in range(n)]
        for pos, s in enumerate(chain.sinks):
            reach[s].add(pos)
        for i in range(n - 1, -1, -1):
            for c in cons[i]:
                reach[i] |= reach[c]
        out = []
        for i, m in enumerate(chain):
            ins = chain.nodes[i]
            if not ins:
                r_in = Fraction(1, 1)
            elif len(ins) == 1:
                r_in = chain.node_ratios[ins[0]]
            else:
                r_in = tuple(chain.node_ratios[j] for j in ins)
            branch = next(iter(reach[i])) if len(reach[i]) == 1 else None
            out.append((m, r_in, chain.node_ratios[i], branch))
        return out
    out = []
    producer = chain.producer if chain.fanout else list(chain)
    r_in = Fraction(1, 1)
    for m in producer:
        r_out = r_in * _member_ratio(m)
        out.append((m, r_in, r_out, None))
        r_in = r_out
    if chain.fanout:
        r_boundary = r_in
        for j, br in enumerate(chain.branches):
            r_in = r_boundary
            for m in br:
                r_out = r_in * _member_ratio(m)
                out.append((m, r_in, r_out, j))
                r_in = r_out
    return out


def _set_member_counters(m, boundary, items: int, r_in,
                         r_out: Fraction) -> None:
    if isinstance(r_in, tuple):
        # a merge member: one in-rate per ordered input port
        for p, r in zip(m.stream_inputs, r_in):
            if id(p) not in boundary:
                p.items_consumed = int(items * r)
    else:
        for p in m.stream_inputs:
            if id(p) not in boundary:      # boundary counters are live
                p.items_consumed = int(items * r_in)
    for p in m.stream_outputs:
        if id(p) not in boundary:
            p.items_produced = int(items * r_out)


def _boundary_ports(fused) -> set:
    """The fused kernel's LIVE port identities (their counters are the
    flowgraph's own; the bridge must not stomp them). Fan-out kernels carry
    one live output per branch."""
    outs = getattr(fused, "outputs", None) or [fused.output]
    return {id(fused.input)} | {id(o) for o in outs}


def _install_bridge(chain: DevChain, fused) -> None:
    """Per-member metrics bridge: each ORIGINAL block keeps reporting its own
    item counters (derived from the fused frame counter through the composed
    rate contract — branch members through THEIR branch's path rate) plus
    ``fused_devchain`` provenance — the devchain analog of fastchain's live
    counter bridge. Fan-out members also report ``devchain_branch`` (their
    branch index; producer members report none)."""
    boundary = _boundary_ports(fused)
    for m, r_in, r_out, branch in _chain_rates(chain):
        if not hasattr(m, "_dc_base_extra"):
            m._dc_base_extra = getattr(m, "extra_metrics", None)
        base_extra = m._dc_base_extra

        def make_extra(m=m, r_in=r_in, r_out=r_out, branch=branch,
                       base_extra=base_extra):
            def extra():
                frames = fused._frames_dispatched
                _set_member_counters(m, boundary, frames * fused.frame_size,
                                     r_in, r_out)
                out = dict(
                    (base_extra() if callable(base_extra) else {}),
                    fused_devchain=True,
                    devchain_frames=frames,
                    devchain_dispatches=fused._dispatches,
                    frames_per_dispatch=fused.k_batch,
                )
                if branch is not None:
                    out["devchain_branch"] = branch
                return out
            return extra

        m.extra_metrics = make_extra()


def _freeze_bridge(chain: DevChain, fused) -> None:
    """Swap the LIVE bridge for a frozen snapshot once the run is over: the
    live closures capture the fused kernel, which would pin its compiled
    executable and device carry (one frame-sized boundary-stash buffer per
    member fence) for as long as anyone keeps the flowgraph around. Post-run
    metrics only need the final numbers."""
    boundary = _boundary_ports(fused)
    frames = fused._frames_dispatched
    for m, r_in, r_out, branch in _chain_rates(chain):
        _set_member_counters(m, boundary, frames * fused.frame_size,
                             r_in, r_out)
        base_extra = getattr(m, "_dc_base_extra", None)
        snap = dict(
            (base_extra() if callable(base_extra) else {}),
            fused_devchain=True,
            devchain_frames=frames,
            devchain_dispatches=fused._dispatches,
            frames_per_dispatch=fused.k_batch,
        )
        if branch is not None:
            snap["devchain_branch"] = branch
        m.extra_metrics = (lambda s=snap: dict(s))


# ---------------------------------------------------------------------------
# supervisor-protocol impersonation + the fused drive loop
# ---------------------------------------------------------------------------

async def _next_msg(inbox):
    """Next inbox message, parking on the coalescing notifier. Returns None on
    a bare notify (the supervisor's start signal is a notify with no message)."""
    msg = inbox.try_recv()
    if msg is not None:
        return msg
    await inbox.wait()
    inbox.take_pending()
    return inbox.try_recv()


async def run_devchain_task(members: Sequence, chain: DevChain, fg_inbox,
                            scheduler) -> None:
    """Impersonate ``members`` (WrappedKernels) at the supervisor protocol
    level while the fused kernel drives the chain: answer the init barrier per
    member (compiling the composed program inside it), run the fused
    TpuKernel's drain loop on a dedicated thread against the chain's own
    boundary buffers, then report per-member BlockDone with counters bridged."""
    from ..types import Pmt
    from .runtime import BlockDoneMsg, BlockErrorMsg, InitializedMsg

    def _finish_all():
        for b in members:
            fg_inbox.send(BlockDoneMsg(b.id, b))

    def _error_out(e):
        log.error("devchain failed (%r)", e)
        fg_inbox.send(BlockErrorMsg(members[0].id, e))
        for b in members[1:]:
            fg_inbox.send(BlockDoneMsg(b.id, b))

    # ---- init barrier for every member (fastchain contract) -----------------
    for b in members:
        while True:
            msg = await _next_msg(b.inbox)
            if isinstance(msg, Initialize):
                break
            if isinstance(msg, Terminate):
                _finish_all()
                return
            if isinstance(msg, Callback):
                msg.reply.set(Pmt.invalid_value())
    member_kernels = [b.kernel for b in members]
    # restart-capable fused chain: the first member carrying a `restart`
    # policy (its own BlockPolicy or the config default — member_ok already
    # refused isolate members) lends the fused kernel its restart
    # budget/backoff and its billing identity
    pol_member = next((b for b in members
                       if b.policy.on_error == "restart"), None)
    try:
        fused = _build_fused(chain)
        # arm the fused kernel's carry checkpointing when the chain can
        # actually restart (tpu/kernel_block.py _resolve_ckpt_every — the
        # fused kernel has no .policy of its own, the members carry it)
        fused._dc_restartable = pol_member is not None
        # compile + warm OFF the supervisor loop: the fused kernel is a
        # BLOCKING block whose init the actor path would run on a dedicated
        # thread — compiling here inline would stall every same-loop block
        # task and serialize multiple devchains' compiles
        await scheduler.spawn_blocking(
            lambda: asyncio.run(fused.init(fused.mio, fused.meta)))
        # a TpuStage queues pre-launch ctrl until its (lazy) carry exists —
        # apply the queue to the FUSED carry now, exactly where the actor
        # path would apply it at first-frame compile (invalid updates were
        # already rejected at queue time; a failure here only logs, as there)
        for idx, k in enumerate(member_kernels):
            for stage, params in getattr(k, "_pending_ctrl", ()):
                try:
                    _apply_stage_update(fused, idx, stage, params)
                except Exception as e:                 # noqa: BLE001
                    log.warning("queued ctrl update rejected: %r", e)
            if getattr(k, "_pending_ctrl", None):
                k._pending_ctrl.clear()
        _install_bridge(chain, fused)
    except Exception as e:                             # noqa: BLE001
        _error_out(e)
        return
    for b in members:
        fg_inbox.send(InitializedMsg(b.id, ok=True))

    # No separate start-wait phase: actor blocks enter their event loop right
    # after init too (WrappedKernel.run), parking until the supervisor's start
    # notify — the drive loop below does the same. A dedicated start phase
    # would have to drain the inbox to find the bare notify and would swallow
    # a StreamInputDone racing it (a fast source can produce AND finish within
    # the first scheduler slice after the barrier releases — observed live;
    # the lost EOS deadlocked the chain). BlockDone before the barrier
    # releases is impossible on the happy path: it needs upstream EOS or
    # Terminate, and producers only run after start.

    # The drive loop merges the inboxes whose ports the fused kernel WORKS:
    # the region input (first member) and every branch tail's output —
    # produce/consume notifications land on THOSE, because the boundary
    # buffers were bound to them at materialize time. Linear chains have one
    # tail (the last member); fan-out regions one per branch.
    if chain.dag:
        tail_idx = list(chain.sinks)
    elif chain.fanout:
        tail_idx = []
        off = len(chain.producer)
        for br in chain.branches:
            off += len(br)
            tail_idx.append(off - 1)
    else:
        tail_idx = [len(members) - 1]
    tail_set = set(tail_idx)
    multi_out = chain.fanout or chain.dag

    # Intermediate members' inboxes: nothing routes data there, but ctrl
    # Calls/Callbacks must reach the drive thread (carry surgery happens
    # between dispatches there) — forward them with the member index.
    async def watch(b, idx):
        while True:
            msg = await _next_msg(b.inbox)
            if isinstance(msg, (Call, Callback)):
                members[0].inbox.send(_FwdCtrl(idx, msg))
            if isinstance(msg, Terminate):
                return                   # the drive loop gets its own copy

    watchers = [asyncio.ensure_future(watch(b, i))
                for i, b in enumerate(members)
                if i != 0 and i not in tail_set]

    first_ib = members[0].inbox
    drive_ibs = [first_ib] + [members[i].inbox for i in tail_idx]
    # inbox identity → the member index its direct Call/Callback addresses,
    # and (for tails) the branch it retires on StreamOutputDone
    member_of_ib = {id(first_ib): 0}
    branch_of_ib = {}
    for j, i in enumerate(tail_idx):
        member_of_ib[id(members[i].inbox)] = i
        branch_of_ib[id(members[i].inbox)] = j

    # On a work-loop fault the drive loop restarts the FUSED kernel in
    # place: checkpoint restore + replay first (bit-correct), forfeiting
    # fresh re-init as the fallback — the "recovery AND fusion" contract of
    # the device-plane recovery PR.
    async def _drive():
        """The fused block event loop (WrappedKernel.run's loop, merged over
        the region's boundary inboxes)."""
        io = WorkIo()
        kernel = fused

        async def _restart_fused(err):
            """One recovery of the fused kernel per work fault, with retries
            out of the policy member's restart budget (the actor-path
            _reinit_for_restart contract): checkpoint restore + replay,
            falling back to a forfeiting fresh init when recovery declines.
            Returns None on success, else the TERMINAL exception — the one
            that actually ended the chain, not the work error the restarts
            were trying to recover from (same reporting contract as the
            actor path)."""
            while pol_member is not None and \
                    pol_member.restarts < pol_member.policy.max_restarts:
                await pol_member._note_restart(err, fg_inbox, phase="work")
                _tel_journal.emit(
                    "devchain", "restart",
                    region=kernel.meta.instance_name,
                    attempt=pol_member.restarts, error=repr(err))
                try:
                    if await kernel.recover(err):
                        log.info("devchain %s recovered in place from its "
                                 "composed-carry checkpoint (replay)",
                                 kernel.meta.instance_name)
                    else:
                        # no usable checkpoint: fresh re-init forfeits the
                        # in-flight window (billed) but keeps the graph alive
                        await kernel.init(kernel.mio, kernel.meta)
                    return None
                except Exception as e2:                # noqa: BLE001
                    log.warning("devchain restart attempt failed (%r)", e2)
                    err = e2
            return err

        def ctrl(idx, msg):
            res = _apply_ctrl(kernel, member_kernels, idx, msg.port, msg.data)
            if isinstance(msg, Callback):
                msg.reply.set(res)

        while True:
            for ib in drive_ibs:
                io.call_again = ib.take_pending() or io.call_again
            for ib in drive_ibs:
                while True:
                    msg = ib.try_recv()
                    if msg is None:
                        break
                    if isinstance(msg, _FwdCtrl):
                        ctrl(msg.idx, msg.msg)
                    elif isinstance(msg, (Call, Callback)):
                        ctrl(member_of_ib[id(ib)], msg)
                    elif isinstance(msg, StreamInputDone):
                        kernel.input.set_finished()
                        io.call_again = True
                    elif isinstance(msg, StreamOutputDone):
                        if multi_out:
                            # one sink's reader detached: retire THAT
                            # branch/sink, the survivors keep streaming (the
                            # port-group rule — a finished reader is dropped,
                            # not fatal); work() finishes the block when
                            # every output retired
                            kernel.retire_branch(branch_of_ib[id(ib)])
                            io.call_again = True
                        else:
                            io.finished = True
                    elif isinstance(msg, Terminate):
                        io.finished = True
            if io.finished:
                break
            if not io.call_again:
                waits = [asyncio.ensure_future(ib.wait())
                         for ib in drive_ibs]
                await asyncio.wait(waits,
                                   return_when=asyncio.FIRST_COMPLETED)
                for w in waits:
                    if not w.done():
                        w.cancel()
                continue
            io.reset()
            try:
                await kernel.work(io, kernel.mio, kernel.meta)
            except Exception as e:                     # noqa: BLE001
                terminal = await _restart_fused(e)
                if terminal is not None:
                    raise terminal
                io.reset()
                io.call_again = True     # re-examine ports now

    def _drive_thread():
        # the fused kernel is BLOCKING (host syncs in the drain): a dedicated
        # thread with a private loop, exactly how the scheduler runs BLOCKING
        # actor blocks
        asyncio.run(_drive())

    def _eos_ports():
        # orderly shutdown: EOS every driven output, detach upstream
        # (block.py contract)
        for o in (getattr(fused, "outputs", None) or [fused.output]):
            o.notify_finished()
        fused.input.notify_finished()

    t_chain = _trace.now()
    try:
        await scheduler.spawn_blocking(_drive_thread)
    except Exception as e:                             # noqa: BLE001
        for w in watchers:
            w.cancel()
        try:
            _eos_ports()
        except Exception:                              # noqa: BLE001
            pass
        _freeze_bridge(chain, fused)
        _error_out(e)
        return
    for w in watchers:
        w.cancel()
    try:
        _eos_ports()
    except Exception as e:                             # noqa: BLE001
        _freeze_bridge(chain, fused)
        _error_out(e)
        return
    # drop the live bridge's reference to the fused kernel (compiled program +
    # boundary-stash device buffers) — final counters are frozen in place
    _freeze_bridge(chain, fused)
    # one span for the whole fused run, per-member frame counters in args —
    # the devchain lane of docs/observability.md; fan-out runs add per-branch
    # attribution (tail, member count, items out, retired early?) so the
    # doctor can say WHICH branch a fused region spent its output on
    span_args = {"members": len(members),
                 "frames": fused._frames_dispatched,
                 "dispatches": fused._dispatches,
                 "frames_per_dispatch": fused.k_batch,
                 "per_member": {b.instance_name: fused._frames_dispatched
                                for b in members}}
    if chain.fanout:
        span_args["branches"] = [
            {"branch": j,
             "tail": members[i].instance_name,
             "members": len(chain.branches[j]),
             "items_out": fused._frames_dispatched * fused.out_frames[j],
             "retired": bool(fused._branch_done[j])}
            for j, i in enumerate(tail_idx)]
    elif chain.dag:
        # general DAG regions: per-SINK attribution + the merge count, so a
        # doctor report names which sink of a fused receiver carried output
        span_args["sinks"] = [
            {"sink": j,
             "tail": members[i].instance_name,
             "items_out": fused._frames_dispatched * fused.out_frames[j],
             "retired": bool(fused._branch_done[j])}
            for j, i in enumerate(tail_idx)]
        span_args["merges"] = sum(1 for ins in chain.nodes if len(ins) > 1)
    _trace.complete(
        "devchain",
        f"devchain[{members[0].instance_name}…x{len(members)}]", t_chain,
        args=span_args)
    _finish_all()
