"""Open-loop online serving through ``ServingRuntime``.

Set-up builds the runtime the way a user does (``hector.compile`` with the
mix's fanout, the device feature store, the coalescer's ladder, then
``ServingRuntime.calibrate``, which compiles the ladder's shape sets), and
serves ``warm_seconds`` of the same traffic drawn from another stream.

The window submits a schedule drawn from the seed: ``rate_rps`` x
``--seconds`` requests (a fixed count, so every seed does the same work),
arrivals spread as a Poisson process conditioned on that count, request
sizes in equal shares of ``sizes`` in a
seeded order, seed nodes uniform over the graph. Each request is timed from
the moment it was due to its completion: the generator's lateness plus the
runtime's own latency. A request that is rejected or never completes is
missing: it counts in ``failed`` and ranks above every completed one.

After the window, with the runtime closed and freed, a sample of the
finished requests drawn from the seed (the longest among them) is checked:

* ``sampler_faults``: the blocks sampled for each sampled request's batch
  must be a valid fanout sample of the graph (every edge exists; per
  destination and relation, min(fanout, in-degree) edges; frontiers chain
  from the seeds inward);
* ``logits_gap``: the logits each request received, against the plain
  reference run on those blocks with features read from the table: the
  largest |difference| in a row over the row's largest |reference logit|
  (or the median such scale, where larger).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np
import jax

from bench.runners import common
from bench.harness import Check, Cell, Outcome, log, memory_peak_bytes
from bench.reference import stack


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def schedule(traffic: dict, seconds: float, num_nodes: int, seed: int
             ) -> List[dict]:
    """The requests of one window: ``arrival_s``, ``seeds``."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    if traffic["process"] != "poisson":
        raise ValueError(f"process {traffic['process']!r}")
    arrivals = np.sort(rng.uniform(0.0, seconds, n))
    sizes = np.resize(np.asarray(traffic["sizes"], np.int64), n)
    rng.shuffle(sizes)
    if traffic["popularity"] != "uniform":
        raise ValueError(f"popularity {traffic['popularity']!r}")
    return [{"arrival_s": float(a),
             "seeds": rng.integers(0, num_nodes, int(s)).astype(np.int32)}
            for a, s in zip(arrivals, sizes)]


# ---------------------------------------------------------------------------
# what the benchmark records of the timed path
# ---------------------------------------------------------------------------
class Recorder:
    """Keeps the blocks sampled for the batches of the requests that will be
    checked, by wrapping the engine's sampler and the coalescer's ``plan``.
    In a traced run it also marks the host's calls into each layer with an
    annotation (``bench.sample``, ``bench.feature_gather``,
    ``bench.execute``, ``bench.coalesce``)."""

    def __init__(self, compiled, store, annotate: bool):
        engine = compiled.engine
        self._sampler = engine.sampler
        self.annotate = annotate
        self.wanted_rids: set = set()
        self.batch_of: Dict[int, object] = {}    # rid -> PlannedBatch
        self.wanted_steps: set = set()
        self.blocks: Dict[int, object] = {}      # step -> BlockSequence
        self._lock = threading.Lock()
        engine.sampler = self
        if annotate:
            store.gather = self._annotated("bench.feature_gather",
                                           store.gather)
            compiled.forward_minibatch = self._annotated(
                "bench.execute", compiled.forward_minibatch)

    @staticmethod
    def _annotated(name: str, fn):
        def call(*args, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kw)
        return call

    def attach(self, coalescer) -> None:
        plan = coalescer.plan
        if self.annotate:
            plan = self._annotated("bench.coalesce", plan)

        def recording_plan(*args, **kw):
            decision = plan(*args, **kw)
            pb = decision.batch
            if pb is not None:
                with self._lock:
                    for r in pb.requests:
                        if r.rid in self.wanted_rids:
                            self.batch_of[r.rid] = pb
                            self.wanted_steps.add(pb.step)
            return decision
        coalescer.plan = recording_plan

    def __getattr__(self, name):
        return getattr(self._sampler, name)

    def reset(self, wanted_rids=()) -> None:
        with self._lock:
            self.wanted_rids = set(wanted_rids)
            self.batch_of.clear()
            self.wanted_steps.clear()
            self.blocks.clear()

    def sample(self, seeds, batch_index: int = 0, epoch=None):
        if self.annotate:
            with jax.profiler.TraceAnnotation("bench.sample"):
                seq = self._sampler.sample(seeds, batch_index=batch_index,
                                           epoch=epoch)
        else:
            seq = self._sampler.sample(seeds, batch_index=batch_index,
                                       epoch=epoch)
        if batch_index in self.wanted_steps:
            self.blocks[batch_index] = seq
        return seq


class Server:
    """The system under test for one seed, set up and warm."""

    def __init__(self, cell: Cell, arrays, annotate: bool = False):
        tr = cell.traffic
        self.cell = cell
        self.num_nodes = int(arrays["node_type"].size)
        self.compiled = common.compile_program(
            cell, arrays, sample=int(tr["fanout"]),
            seed=common.seed_int(cell.seed, common.TAG_SAMPLER))
        params, feats, _ = common.make_inputs(cell, self.num_nodes)
        common.check_param_structure(self.compiled, params)
        self.params = params
        self.store = self.compiled.make_feature_store(feats)
        del feats
        self.recorder = Recorder(self.compiled, self.store, annotate)
        self.runtime = None
        self.restart()
        self.next_rid = 0

    def restart(self) -> None:
        """A new runtime, calibrated and started, on the same program,
        weights and feature store (its latency model starts afresh)."""
        from repro.serve import ServingRuntime, ladder
        if self.runtime is not None:
            self.runtime.close()
        tr = self.cell.traffic
        self.runtime = ServingRuntime(
            self.compiled, self.params, self.store,
            rungs=ladder(int(tr["max_batch"]), tr["ladder"]),
            max_batch=int(tr["max_batch"]),
            max_wait_ms=float(tr["max_wait_ms"]))
        self.recorder.attach(self.runtime.coalescer)
        self.runtime.calibrate()
        self.runtime.start()

    def serve(self, requests: List[dict], slo_ms: float,
              annotate: bool = False) -> List[dict]:
        """Submit ``requests`` on their schedule (open loop); wait for every
        one to end. Returns per request: rid, due and submit times,
        response."""
        from repro.serve import Request
        out = []
        t0 = time.monotonic()
        for r in requests:
            due = t0 + r["arrival_s"]
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            req = Request(rid=self.next_rid, seeds=r["seeds"],
                          arrival_s=r["arrival_s"], slo_ms=slo_ms)
            self.next_rid += 1
            if annotate:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = self.runtime.submit(req)
            else:
                h = self.runtime.submit(req)
            out.append({"rid": req.rid, "due": due, "request": req,
                        "handle": h})
        return out

    def wait(self, sent: List[dict], wait_s: float = 60.0) -> None:
        end = time.monotonic() + wait_s
        for s in sent:
            s["response"] = s["handle"].wait(max(0.0, end - time.monotonic()))

    def close(self) -> None:
        self.runtime.close()
        self.runtime = self.compiled = self.params = self.store = None


def latencies_ms(sent: List[dict]) -> np.ndarray:
    """Due time to completion per request; inf where it never completed."""
    out = np.full(len(sent), np.inf)
    for i, s in enumerate(sent):
        resp = s.get("response")
        if resp is not None and resp.completed:
            done = s["request"].t_arrive + resp.latency_ms * 1e-3
            out[i] = (done - s["due"]) * 1e3
    return out


def lateness_ms(sent: List[dict]) -> np.ndarray:
    return np.asarray([(s["request"].t_arrive - s["due"]) * 1e3
                       for s in sent])


def pick_checked(sizes: np.ndarray, count: int, seed: int) -> List[int]:
    """Request indices drawn from the seed: ``count`` requests in a seeded
    order, the longest request among them."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(sizes))
    longest = [i for i in order if sizes[i] == sizes.max()]
    pick = list(order[:count])
    if longest and longest[0] not in pick:
        pick[-1] = longest[0]
    return [int(i) for i in pick]


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
class GraphIndex:
    """The graph's edges as sorted keys ``(dst * R + etype) * N + src``."""

    def __init__(self, arrays, num_etypes: int):
        self.n = int(arrays["node_type"].size)
        self.r = int(num_etypes)
        self.keys = np.sort(self._key(arrays["dst"], arrays["etype"],
                                      arrays["src"]))

    def _key(self, dst, et, src):
        return ((np.asarray(dst, np.int64) * self.r
                 + np.asarray(et, np.int64)) * self.n
                + np.asarray(src, np.int64))

    def count(self, dst, et, src) -> np.ndarray:
        k = self._key(dst, et, src)
        return (np.searchsorted(self.keys, k, side="right")
                - np.searchsorted(self.keys, k, side="left"))

    def degree(self, dst, et) -> np.ndarray:
        lo = self._key(dst, et, 0)
        return (np.searchsorted(self.keys, lo + self.n, side="left")
                - np.searchsorted(self.keys, lo, side="left"))


def sampler_faults(seq, seeds: np.ndarray, index: GraphIndex,
                   fanout: int) -> int:
    """How many ways the batch's blocks break fanout sampling."""
    faults = 0
    frontier = np.unique(seeds)
    for block in reversed(seq.blocks):       # from the seeds inward
        ids = np.asarray(block.node_ids)
        g = block.graph
        dst_ids = ids[np.asarray(block.dst_local)]
        if not np.array_equal(dst_ids, frontier):
            faults += 1
        src = ids[np.asarray(g.src)]
        dst = ids[np.asarray(g.dst)]
        et = np.asarray(g.etype)
        if not np.array_equal(ids, np.unique(np.concatenate([frontier,
                                                              src]))):
            faults += 1
        faults += int(np.sum(~np.isin(dst, frontier)))
        # multiset: no (src, dst, etype) more often than the graph has it
        k = (index._key(dst, et, src))
        uk, cnt = np.unique(k, return_counts=True)
        have = (np.searchsorted(index.keys, uk, side="right")
                - np.searchsorted(index.keys, uk, side="left"))
        faults += int(np.sum(cnt > have))
        # per (destination, relation): min(fanout, in-degree) edges
        fd = np.repeat(frontier, index.r)
        fe = np.tile(np.arange(index.r), frontier.size)
        want = np.minimum(index.degree(fd, fe), fanout)
        pair = (np.searchsorted(frontier, dst) * index.r + et)
        got = np.bincount(pair, minlength=frontier.size * index.r)
        faults += int(np.sum(got != want))
        frontier = ids
    return faults


def block_hops(seq) -> List[dict]:
    """A batch's blocks as the reference reads them (innermost first)."""
    return [{"src": np.asarray(b.graph.src), "dst": np.asarray(b.graph.dst),
             "etype": np.asarray(b.graph.etype),
             "num_nodes": int(b.node_ids.size),
             "dst_local": np.asarray(b.dst_local)} for b in seq.blocks]


def reference_logits(cell: Cell, seq, params, table, sizes, precision: str
                     ) -> np.ndarray:
    """The reference's logits for every seed of a batch, in seed order."""
    x0 = np.asarray(table[jax.numpy.asarray(seq.blocks[0].node_ids)])
    out = stack.forward_blocks(stack.model(cell.config["reference"], cell.root),
                               params, x0, block_hops(seq),
                               cell.config["graph"]["num_etypes"], sizes,
                               precision)
    return out[np.asarray(seq.seed_perm)]


def logits_gap(served: List[np.ndarray], ref: List[np.ndarray]) -> float:
    scales = [float(np.max(np.abs(r))) for r in ref]
    med = float(np.median(scales)) if scales else 0.0
    gaps = [float(np.max(np.abs(s - r))) / max(sc, med, 1e-30)
            for s, r, sc in zip(served, ref, scales)]
    return max(gaps) if gaps else float("inf")


CHECKED = ("sampler_faults", "logits_gap")


def check_batches(cell: Cell, arrays, checked: List[dict], recorder,
                  precision: str = "highest", index=None) -> Dict:
    """Sampler faults and the logits gap of the checked requests (rows of
    served logits against the reference on their batch's blocks)."""
    index = index or GraphIndex(arrays, cell.config["graph"]["num_etypes"])
    params, table, _ = common.make_inputs(cell, int(arrays["node_type"].size))
    params = stack.host_params(params)
    faults, served, ref = 0, [], []
    by_step: Dict[int, list] = {}
    for s in checked:
        pb = recorder.batch_of.get(s["rid"])
        seq = None if pb is None else recorder.blocks.get(pb.step)
        if seq is None or not np.array_equal(seq.seeds, pb.seeds):
            faults += 1
            continue
        by_step.setdefault(pb.step, []).append(s)
    fanout = int(cell.traffic["fanout"])
    r = cell.config["graph"]["num_etypes"]
    seqs = [recorder.blocks[step] for step in by_step]
    sizes = [stack.hop_sizes([block_hops(q)[i] for q in seqs], r)
             for i in range(len(seqs[0].blocks))] if seqs else []
    for step, group in sorted(by_step.items()):
        pb = recorder.batch_of[group[0]["rid"]]
        seq = recorder.blocks[step]
        faults += sampler_faults(seq, pb.seeds, index, fanout)
        logits = reference_logits(cell, seq, params, table, sizes,
                                  precision)
        for s in group:
            i = [r.rid for r in pb.requests].index(s["rid"])
            lo, hi = pb.slices[i]
            got = s["response"].logits
            if got is None or got.shape != (hi - lo, logits.shape[1]):
                faults += 1
                continue
            served.append(np.asarray(got, np.float64))
            ref.append(logits[lo:hi].astype(np.float64))
    return {"sampler_faults": float(faults),
            "logits_gap": logits_gap(served, ref), "rows": sum(
                r.shape[0] for r in ref), "served": served, "ref": ref}


# ---------------------------------------------------------------------------
def measure(cell: Cell, devices, counter) -> Dict:
    """Set up, warm up, serve the window, read the device's peak memory and
    free the program. Returns what the metrics and the check read."""
    from repro import obs
    tr = cell.traffic
    arrays = common.load_arrays(cell)
    server = Server(cell, arrays, annotate=cell.trace)
    warm = schedule(tr, float(tr["warm_seconds"]), server.num_nodes,
                    common.seed_int(cell.seed, common.TAG_WARM))
    server.wait(server.serve(warm, float(tr["slo_ms"])))
    requests = schedule(tr, cell.seconds, server.num_nodes,
                        common.seed_int(cell.seed, common.TAG_TRAFFIC))
    # twice the checked count is recorded: some may not finish
    picks = pick_checked(np.asarray([r["seeds"].size for r in requests]),
                         int(tr["check_requests"]) * 2,
                         common.seed_int(cell.seed, common.TAG_CHECK))
    server.recorder.reset(server.next_rid + i for i in picks)
    setup_s = time.perf_counter() - cell.t_start
    log(f"[setup] {setup_s:.3f} s; ladder {server.runtime.coalescer.rungs}")

    ex = server.compiled.engine.block_executor
    compiles0, traces0 = counter.count, ex.trace_count
    with obs.scope(metrics=False, tracing=cell.trace) as sc:
        with cell.window():
            sent = server.serve(requests, float(tr["slo_ms"]),
                                annotate=cell.trace)
        server.wait(sent)
        spans = sc.tracer.events() if cell.trace else []
    window_compiles = counter.count - compiles0 + ex.trace_count - traces0
    lat = latencies_ms(sent)
    late = lateness_ms(sent)
    failed = int(np.sum(~np.isfinite(lat)))
    p95 = common.percentile_nearest(lat, 95)
    if not np.isfinite(p95):
        p95 = cell.seconds * 1e3
    log(f"[window] {len(sent)} requests, {failed} missing; p50 "
        f"{common.percentile_nearest(lat, 50):.3f} ms, p95 {p95:.3f} ms; "
        f"generator lateness p95 {common.percentile_nearest(late, 95):.3f} "
        f"ms, max {float(np.max(late)):.3f} ms")
    peak = memory_peak_bytes(devices)
    recorder = server.recorder
    server.close()
    del server
    common.free_device()

    finished = [sent[i] for i in picks
                if sent[i].get("response") is not None
                and sent[i]["response"].completed]
    checked = finished[:int(tr["check_requests"])]
    longest = [s for s in finished
               if s["request"].num_seeds == max(tr["sizes"])]
    if longest and longest[0] not in checked:
        checked.append(longest[0])
    queue = [s["response"].queue_ms for s in sent
             if s.get("response") is not None and s["response"].completed]
    return {"arrays": arrays, "sent": sent, "checked": checked,
            "recorder": recorder, "setup_s": setup_s, "p95": p95,
            "failed": failed, "peak": peak, "spans": spans, "queue": queue,
            "window_compiles": window_compiles}


def run(cell: Cell, devices, counter) -> Outcome:
    m = measure(cell, devices, counter)
    t_ref = time.perf_counter()
    res = check_batches(cell, m["arrays"], m["checked"], m["recorder"])
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s; "
        f"{len(m['checked'])} requests, {res['rows']} rows; sampler faults "
        f"{res['sampler_faults']}, logits gap {res['logits_gap']!r}")
    checks = [Check(k, res[k], cell.limit(k)) for k in CHECKED]
    return Outcome(attempted=len(m["sent"]), failed=m["failed"],
                   metrics={"request_p95_ms": m["p95"],
                            "setup_s": m["setup_s"]},
                   checks=checks, memory_peak_bytes=m["peak"],
                   layer={"queue_ms": m["queue"], "spans": m["spans"]},
                   window_compiles=m["window_compiles"])
