"""One run of one cell: set up, warm up, measure, check, report.

The cell states deployment facts only (dataset, width, scale, scope
strategy, executor, precision, k). Every other choice — kernel, scheduler
configuration, maintenance cadence, degradation — stays the program's
default, so a PR that improves a default shows as a gain.
"""
from __future__ import annotations

import collections
import gc
import importlib.util
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import load, peaks as peaks_mod, reference, trace as trace_mod, twin

BENCH = Path(__file__).resolve().parents[1]
GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- metrics
@dataclass
class RunData:
    """Everything a metric reader may read from one run."""
    cell: dict
    config: dict
    traffic: dict
    window: load.Window
    setup_s: float
    peak_bytes: int
    corpus_bytes: int
    compiles_in_window: int
    reduction: Optional[trace_mod.Reduction] = None
    peaks: Optional[peaks_mod.Peaks] = None
    least_bytes: Optional[float] = None
    grace_s: float = GRACE_S

    def batches(self) -> List[object]:
        """The shared accounting of every batch that answered a query of
        the window, once each."""
        seen: Dict[int, object] = {}
        for q in self.window.queries:
            if q.ok and q.batch is not None:
                seen.setdefault(id(q.batch), q.batch)
        return list(seen.values())


def _load(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported once."""
    mod_name = f"bench_{kind}_{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            mod_name, BENCH / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def load_reader(name: str) -> Callable[[RunData], Optional[float]]:
    """The reader of metric ``name``: ``read`` in
    ``bench/metrics/<name>.py``."""
    return _load("metrics", name).read


def load_loop(name: str):
    """The load generator ``name`` that a traffic mix's ``loop`` names:
    ``bench/loops/<name>.py``, with ``build_stream(cfg, traffic, corpus,
    seed)`` (a stream with ``dsm``, the MOVE/MERGE ops, and ``entries``,
    the entry behind each query-vector row), ``drive(sched, slot, stream,
    qvecs, traffic, seconds, grace)`` (the measured window) and
    ``timeline(stream, n)`` (the control's layout of the first n
    queries)."""
    return _load("loops", name)


def read_metrics(specs: Sequence[dict], run: RunData) -> Dict[str, dict]:
    out = {}
    for m in specs:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ set-up
def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or ``$JAX_COMPILATION_CACHE_DIR``), caching every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (or loads from the persistent cache) while
    ``armed``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.armed = False
        self.count = 0
        self.total = 0
        self.names: List[str] = []          # of the armed ones
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.event:
            self.total += 1
            if self.armed:
                self.count += 1
                self.names.append(str(kw.get("fun_name", "")))


def warm_scopes(scopes: Sequence[Tuple[str, bool, int]]
                ) -> Tuple[List[Tuple[str, bool]], List[Tuple[str, bool]]]:
    """Scopes whose launches cover every shape the traffic can form: the
    smallest and the largest scope of each power-of-two size band (so a
    plan crossover inside a band is met on both sides), one scope of each
    exact size up to 16 (result widths below k), and the 32 broadest
    scopes."""
    lo: Dict[int, Tuple[int, str, bool]] = {}
    hi: Dict[int, Tuple[int, str, bool]] = {}
    exact: Dict[int, Tuple[str, bool]] = {}
    for anchor, rec, size in scopes:
        band = size.bit_length()
        if size > hi.get(band, (0,))[0]:
            hi[band] = (size, anchor, rec)
        if size < lo.get(band, (size + 1,))[0]:
            lo[band] = (size, anchor, rec)
        if size <= 16:
            exact.setdefault(size, (anchor, rec))
    picks = ({(a, r) for _, a, r in [*lo.values(), *hi.values()]}
             | set(exact.values()))
    broad = sorted(scopes, key=lambda s: -s[2])[:32]
    return sorted(picks), [(a, r) for a, r, _ in broad]


def warm_up(sched, scopes, qvecs: np.ndarray, max_batch: int) -> int:
    """Drive the cell's own served path (``pump``) over every batch shape
    it can form: groups of each size band per warm scope, and batches of
    each (requests, distinct broad scopes) pair. Returns the number of
    batches run."""
    picks, broad = warm_scopes(scopes)
    # one size in every power-of-two band: whatever power-of-two rounding
    # the program pads a launch axis with, each padded size is met
    sizes = sorted({g for g in (1, 2, 3, 5, 9, 17, 33, 65, 129, max_batch)
                    if g <= max_batch})
    batches: List[List[Tuple[str, bool]]] = []
    for g in sizes:
        per = max(max_batch // g, 1)
        for lo in range(0, len(picks), per):
            batches.append([s for s in picks[lo: lo + per] for _ in range(g)])
    for b in sizes:
        for s in sizes:
            if s <= b and s <= len(broad):
                batches.append([broad[i % s] for i in range(b)])
    for i, batch in enumerate(batches):
        tickets = [sched.submit(qvecs[(i + j) % len(qvecs)], a, recursive=r)
                   for j, (a, r) in enumerate(batch)]
        while sched.pump():
            pass
        for t in tickets:
            t.result(timeout=600.0)
    return len(batches)


# -------------------------------------------------------------------- run
@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True) -> dict:
    """One run; returns the result object. Raises :class:`NoChip` before
    any work when JAX sees no TPU (``require_tpu``) or too few chips."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX sees {dev.platform!r}, not a TPU")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips; JAX sees "
                     f"{len(devices)}")
    pk = peaks_mod.lookup(dev.device_kind) if require_tpu else None
    root = BENCH.parent
    sys.path.insert(0, str(root / "src"))
    from repro.kernels import ops as kops
    from repro.serving.scheduler import ScheduledDSQ
    from repro.vectordb import DirectoryVectorDB

    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache(root)}")
    counter = CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    t = time.perf_counter()
    corpus = twin.build_corpus(cfg)
    vectors = twin.device_vectors(corpus, seed)
    loop = load_loop(traffic["loop"])
    st = loop.build_stream(cfg, traffic, corpus, seed)
    qvecs = twin.query_vectors(corpus, vectors, st.entries, seed)
    t_gen = time.perf_counter() - t

    t = time.perf_counter()
    db = DirectoryVectorDB(dim=corpus.dim, metric=cfg["metric"],
                           scope_strategy=cfg["scope_strategy"])
    extra = {name: ns.entry_paths() for name, ns in corpus.namespaces.items()
             if name != corpus.query_ns}
    db.ingest(vectors, corpus.primary.entry_paths(), namespaces=extra or None)
    db.build_ann(cfg["executor"])
    db.store.device_vectors().block_until_ready()
    t_ingest = time.perf_counter() - t

    slot = load.DsmSlot(db, st.dsm, corpus.query_ns)
    sched = ScheduledDSQ(db, k=int(cfg["k"]), namespace=corpus.query_ns,
                         executor=cfg["executor"],
                         precision=cfg["precision"], maintenance=slot)
    max_batch = sched.scheduler.cfg.max_batch
    t = time.perf_counter()
    n_warm = warm_up(sched, twin.scope_sizes(corpus.primary), qvecs,
                     max_batch)
    t_warm = time.perf_counter() - t
    log(f"setup entries={corpus.n_entries} dirs={len(corpus.primary.tree)} "
        f"live_dirs={len(db.namespaces[corpus.query_ns].list_dirs())} "
        f"dim={corpus.dim} generate_s={t_gen:.3f} ingest_s={t_ingest:.3f} "
        f"warmup_s={t_warm:.3f} warmup_batches={n_warm} "
        f"compiles={counter.total} max_batch={max_batch} "
        f"dsm_ops_drawn={len(st.dsm)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    launches0 = kops.launch_counts()
    sched.start()
    counter.armed = True
    setup_s = time.perf_counter() - t_start
    win = loop.drive(sched, slot, st, qvecs, traffic, seconds, GRACE_S)
    counter.armed = False
    with slot.lock:
        slot.stamp(final=True)
    sched.stop()
    if trace:
        jax.profiler.stop_trace()
    launches = collections.Counter(kops.launch_counts())
    launches.subtract(launches0)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    plans = collections.Counter(q.plan for q in win.queries if q.ok)
    log(f"served plans={dict(plans)} "
        f"launches={ {k: v for k, v in launches.items() if v} } "
        f"window_compiles={counter.count} {counter.names} "
        f"dsm_groups={len(win.groups)} "
        f"scheduler_cfg={sched.scheduler.cfg} "
        f"maintenance_error={sched.scheduler.maintenance_error!r} "
        f"health={sched.health}")
    dir_set = {"/" + "".join(s + "/" for s in p) if p else "/"
               for p in db.namespaces[corpus.query_ns].list_dirs()}
    del sched, db, slot
    gc.collect()

    reduction = None
    if trace:
        reduction = trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    verdict, least_bytes, final_dirs = check(corpus, vectors, qvecs, win)
    t_check = time.perf_counter() - t
    dsm_ops = win.ops
    rejected = sum(1 for o in dsm_ops if o.error)
    never = sum(1 for o in dsm_ops if o.t_done != o.t_done)
    numbers = dict(verdict.numbers())
    numbers["dsm_rejected"] = rejected
    numbers["dir_mismatch"] = len(dir_set ^ set(final_dirs))
    limits = cfg["limits"]
    correct = bool(verdict.checked) and all(
        numbers[k] <= limits[k] for k in numbers)

    run_data = RunData(cell={"name": cell.name, "chips": cell.chips},
                       config=cfg, traffic=traffic, window=win,
                       setup_s=setup_s, peak_bytes=peak,
                       corpus_bytes=corpus.n_entries * corpus.dim * 4,
                       compiles_in_window=counter.count,
                       reduction=reduction, peaks=pk,
                       least_bytes=least_bytes)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           run_data)
    failed_q = sum(1 for q in win.queries if not q.ok)
    lat = [(q.t_recv - q.t_sched) * 1e3 for q in win.queries if q.ok]
    dlat = [(o.t_done - o.t_sched) * 1e3 for o in dsm_ops if o.t_done == o.t_done]
    log(f"window queries={len(win.queries)} answered={len(lat)} "
        f"failed={failed_q} dsq_p50_ms={load.percentile(lat, 50)!r} "
        f"dsm_ops={len(dsm_ops)} applied={len(dlat) - rejected} "
        f"rejected={rejected} never_applied={never} "
        f"dsm_p50_ms={load.percentile(dlat, 50)!r} "
        f"check_s={t_check:.3f} checked={verdict.checked} "
        f"score_err={verdict.score_err!r} rank_gap={verdict.rank_gap!r}")
    errors = collections.Counter(q.error.split("(")[0] for q in win.queries
                                 if q.error)
    if errors:
        log(f"errors {dict(errors)}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    result = {"correct": correct,
              "attempted": len(win.queries) + len(dsm_ops),
              "failed": failed_q + rejected + never,
              "metrics": metrics, "device": device}
    if reduction is not None:
        ops = sorted(reduction.op_seconds.items(), key=lambda kv: -kv[1])
        result["breakdown"] = {"device_ops": [[k, v] for k, v in ops[:10]],
                               "idle_gaps": [[k, v] for k, v in
                                             reduction.idle_gaps[:10]]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in numbers}
    return result


def check(corpus: twin.Corpus, vectors: np.ndarray, qvecs: np.ndarray,
          win: load.Window) -> Tuple[reference.Verdict, float, List[str]]:
    """Judge every answered query against the plain reference on the tree
    as the DSM groups before its batch left it. Returns the verdict, the
    least bytes the batches answered inside the window had to read, and
    the reference's directory set after every applied op."""
    import jax.numpy as jnp
    ns = corpus.primary
    state = reference.ScopeState(ns.tree.paths(), ns.entry_dir,
                                 twin.live_nodes(ns.tree, ns.entry_dir))
    x = jnp.asarray(vectors)
    by_epoch: Dict[int, List[load.Query]] = collections.defaultdict(list)
    for q in win.queries:
        if q.ok:
            by_epoch[q.epoch].append(q)
    bounds = [0] + list(win.groups)
    applied = 0
    wrong = 0
    score_err = rank_gap = 0.0
    checked = 0
    least = 0.0
    for epoch in range(len(bounds)):
        while applied < bounds[epoch]:
            o = win.ops[applied]
            if not o.error:
                state.apply(o.kind, o.src, o.dst)
            applied += 1
        qs = by_epoch.get(epoch)
        if not qs:
            continue
        ranges = np.asarray([state.scope(q.anchor, q.recursive) for q in qs],
                            np.int64)
        ids = np.stack([q.ids for q in qs])
        scores = np.stack([q.scores for q in qs])
        ans = reference.answer(x, qvecs[[q.vec for q in qs]],
                               state.entry_rank(), ranges, ids)
        v = reference.judge(ids, scores, ans)
        wrong += v.wrong_answers
        score_err = max(score_err, v.score_err)
        rank_gap = max(rank_gap, v.rank_gap)
        checked += v.checked
        batches: Dict[int, List[int]] = collections.defaultdict(list)
        for j, q in enumerate(qs):
            if q.t_recv <= win.seconds:
                batches[id(q.batch)].append(j)
        sorted_rank = np.sort(state.entry_rank())
        for rows in batches.values():
            least += reference.scope_bytes(
                sorted_rank, [tuple(ranges[j]) for j in rows], corpus.dim,
                len(rows))
    del x
    return (reference.Verdict(wrong, score_err, rank_gap, checked), least,
            state.dirs())
