"""The benchmark harness: one run of one cell.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``
with its plain reference ``configs/<name>.py``) and a traffic mix
(``traffic/<name>.json``); each per-layer metric is read by
``metrics/<name>.py``. All are found by name, so a cell, a configuration,
a mix or a metric is added by adding files.

The program is driven through its entry points only: ``Trainer`` with
``CheckNRunManager`` over a ``LocalFSStore`` on the machine's disk, the
batches through ``Trainer(batch_fn=...)`` from ``bench_gen`` and the seed,
the state from the configuration's cell builder. The harness's own spans
wrap the calls into each layer: ``BenchTrainer.checkpoint`` (snapshot and
non-overlap wait), ``TimedStore.put`` (every blob; the manifest put is
the commit), ``TimedManager.restore`` (fetch, decode, apply) and the
placement of the restored state.

A traffic mix's ``mode`` is ``train`` (train and save through the
window) or ``resume`` (write a chain in set-up, then resume from it over
and over in the window).

A configuration's ``program`` block names the program's builder:
``arch``, ``config_class`` and ``shape``, and optionally the device mesh
of its deployment, ``"mesh": {"shape": [2, 2], "axes": ["data",
"model"]}``, with the axis names of the program's sharding rules
(``dist/sharding.recsys_rules``: ``batch`` over ``data``, table rows over
``("data", "model")``). A cell of such a configuration asks for as many
chips as the mesh holds; the harness builds the mesh over the first of
them, passes it to the builder and draws the seed's state already
sharded. Without the key the configuration runs on one device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORK = os.path.join(HERE, ".work")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's records."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------- loading
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_bench() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str) -> tuple:
    """(end-to-end metrics, per-layer metrics) the cell reports: those
    listing it under ``workloads``, and the end-to-end metrics without the
    key (``setup_s``)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    per = [m for m in bench["per_layer"] if cell in m["workloads"]]
    return e2e, per


def load_reader(metric: str) -> Callable:
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ compile log
class CompileLog:
    """Backend compilations and compile-cache loads seen by
    ``jax.monitoring`` after ``mark()``."""

    def __init__(self) -> None:
        self.events: List[tuple] = []
        self._mark = 0

    def __call__(self, event, duration, **kw):
        if event in (BACKEND_COMPILE, CACHE_LOAD):
            self.events.append((event, kw.get("fun_name", "?"), duration))

    def mark(self) -> None:
        self._mark = len(self.events)

    def since_mark(self, event: str) -> List[str]:
        return [n for e, n, _ in self.events[self._mark:] if e == event]


# ------------------------------------ the program (imported when first used)
def build_bundle(cfg: dict, seed: int, mesh=None):
    """The configuration's cell through the program's builder, with its
    state drawn from the seed in one jitted call on the device; over
    ``mesh``, drawn already sharded by the program's rules."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs._families import recsys_cell

    prog = cfg["program"]
    mod_name, cls_name = prog["config_class"].rsplit(".", 1)
    cls = getattr(importlib.import_module(mod_name), cls_name)
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in cfg.items() if k in fields}
    base = recsys_cell(prog["arch"], cls(**kw), prog["shape"], mesh=mesh)
    if mesh is None:
        make = jax.jit(base.make_state)
    else:
        make = jax.jit(base.make_state, out_shardings=jax.tree.map(
            lambda p: NamedSharding(mesh, p), base.state_pspecs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec)))
    key = jax.random.key(seed)
    bundle = dataclasses.replace(base)
    # the program's Trainer draws its state with bundle.make_state()
    bundle.make_state = lambda key_=None: make(key)
    return bundle, base


def checkpoint_config(traffic: dict):
    from repro.core import CheckpointConfig
    from repro.core.quantize import QuantConfig

    ck = dict(traffic["checkpoint"])
    q = ck.pop("quant")
    return CheckpointConfig(quant=QuantConfig(**q), **ck)


class Spans:
    """The harness's host spans: kept in memory, and written into the
    profiler's trace as ``TraceAnnotation`` spans when one runs."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.records: Dict[str, List[tuple]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                t1 = time.monotonic()
                with self.lock:
                    self.records.setdefault(name, []).append((t0, t1))


def timed_store(root: str, spans: Spans):
    """A ``LocalFSStore`` at ``root`` with every put inside a span and each
    manifest commit's time kept by step (``.commits``)."""
    from repro.core import LocalFSStore

    class TimedStore(LocalFSStore):
        def put(self, key: str, data: bytes) -> None:
            with spans.span("put"):
                super().put(key, data)
            if key.startswith("manifests/ckpt_"):
                self.commits[int(key[len("manifests/ckpt_"):-5])] = \
                    time.monotonic()

    store = TimedStore(root)
    store.commits: Dict[int, float] = {}
    return store


def make_trainer_class(spans: Spans):
    from repro.core.checkpoint import CheckNRunManager
    from repro.train.loop import Trainer

    class TimedManager(CheckNRunManager):
        def restore(self, *a, **kw):
            with spans.span("restore"):
                return super().restore(*a, **kw)

    class BenchTrainer(Trainer):
        """The program's Trainer with the harness's span around each
        ``checkpoint()`` (snapshot copy and the non-overlap wait) and a
        hook that records what the check needs of the state at each
        save's boundary, before the snapshot is taken."""

        def __init__(self, *a, on_boundary=None, **kw):
            super().__init__(*a, **kw)
            self.manager.close()
            self.manager = TimedManager(self.manager.store, self.ckpt_cfg)
            self.on_boundary = on_boundary
            self.starts: List[float] = []
            self.stalls: List[float] = []

        def checkpoint(self) -> None:
            if self.on_boundary is not None:
                self.on_boundary(self)
            t0 = time.monotonic()
            with spans.span("checkpoint"):
                super().checkpoint()
            self.starts.append(t0)
            self.stalls.append(time.monotonic() - t0)

    return BenchTrainer


@contextlib.contextmanager
def timed_placement(spans: Spans):
    """Time the program's ``restore_train_state`` through
    ``block_until_ready``: the host→device placement of a restore."""
    import jax
    from repro.train import loop

    orig = loop.restore_train_state

    def placed(*a, **kw):
        with spans.span("place"):
            out = orig(*a, **kw)
            jax.block_until_ready(out)
        return out

    loop.restore_train_state = placed
    try:
        yield
    finally:
        loop.restore_train_state = orig


# ---------------------------------------------------- what the check keeps
class Sampler:
    """Rows and checksums of the live state, taken at a boundary: for each
    table ``rows_per_table`` rows — half drawn from the ids of the
    interval's last batch (rows the interval touched), half uniform — their
    values and row state; and (sum, weighted sum) of the words of every
    dense leaf and its optimizer state. One jitted gather; the rows are
    drawn from the seed and the boundary's step."""

    def __init__(self, bundle, seed: int, rows_per_table: int,
                 fields: Callable[[str], int]):
        import jax
        import jax.numpy as jnp

        self.seed, self.r = seed, rows_per_table
        self.names = list(bundle.tracked)
        self.rows = {n: bundle.tracked[n].rows for n in self.names}
        self.fields = fields

        def words(leaf):
            w = jax.lax.bitcast_convert_type(
                leaf.astype(jnp.float32).reshape(-1), jnp.uint32)
            i = jnp.arange(1, w.size + 1, dtype=jnp.uint32)
            return jnp.stack([jnp.sum(w, dtype=jnp.uint32),
                              jnp.sum(w * i, dtype=jnp.uint32)])

        def take(state, idx):
            tabs = {n: state.params["tables"][n][idx[n]] for n in self.names}
            accs = {n: state.opt_state["tables"][n][idx[n]]
                    for n in self.names}
            dense = [words(x) for x in jax.tree.leaves(
                (state.params["dense"], state.opt_state["dense"]))]
            return tabs, accs, dense

        self._take = jax.jit(take)

    def draw(self, step: int, batch: Optional[dict]) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, step, 0xB0])
        half = self.r // 2
        out = {}
        for n in self.names:
            uni = rng.integers(0, self.rows[n], self.r - half)
            if batch is not None:
                col = batch["sparse_ids"][:, self.fields(n), :].reshape(-1)
                hot = col[rng.integers(0, len(col), half)]
            else:
                hot = rng.integers(0, self.rows[n], half)
            out[n] = np.concatenate([hot, uni]).astype(np.int32)
        return out

    def take(self, state, idx):
        return self._take(state, idx)

    @staticmethod
    def fetch(taken) -> dict:
        import jax

        tabs, accs, dense = jax.device_get(taken)
        return dict(vals={n: np.asarray(v) for n, v in tabs.items()},
                    acc={n: np.asarray(v) for n, v in accs.items()},
                    dense=sorted(tuple(int(x) for x in d) for d in dense))


def memory_peaks(devices) -> List[int]:
    """``peak_bytes_in_use`` of each device (0 where the backend keeps no
    statistics)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def warm_buckets(traffic: dict, dims: List[int]) -> None:
    """Compile (or load from the cache) the quantize+pack and chunk-hash
    programs of every power-of-two row bucket a chunk of this mix can land
    in, for every table width: incremental chunks are ragged. A full save
    has the same chunks every time, so the set-up's save warms them."""
    from repro.kernels.adaptive_quant import quant_pack
    from repro.kernels.chunk_hash.ops import _impl_for, chunk_hash32_device

    ck = traffic["checkpoint"]
    q = ck["quant"]
    impl = ck.get("quant_impl", "auto")
    rows = 256
    while rows <= ck["chunk_rows"]:
        for dim in sorted(set(dims)):
            pq = quant_pack(np.zeros((rows, dim), np.float32), bits=q["bits"],
                            method=q["method"], num_bins=q["num_bins"],
                            ratio=q["ratio"], impl=impl)
            nbytes = (pq.count * q["bits"] + 7) // 8
            chunk_hash32_device(pq.words, count=(nbytes + 3) // 4,
                                impl=_impl_for(impl))
        rows *= 2


# ------------------------------------------------------------------- runs
@dataclasses.dataclass
class RunRecord:
    """What a run measured; the per-layer readers read it."""
    cell: dict
    cfg: dict
    traffic: dict
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: int = 0
    samples: int = 0
    saves: List[dict] = dataclasses.field(default_factory=list)
    restores: List[dict] = dataclasses.field(default_factory=list)
    trace: Any = None
    failed: int = 0
    attempted: int = 0
    compiles_in_window: List[str] = dataclasses.field(default_factory=list)
    loads_in_window: List[str] = dataclasses.field(default_factory=list)


class Run:
    """One run of one cell. ``execute`` returns the result line's dict."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, peaks: dict,
                 compiles: CompileLog, devices: list, mesh=None):
        from bench_gen import BatchGen
        from bench_reftrain import load_model

        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.compiles, self.devices = compiles, devices
        self.rec = RunRecord(cell=cell, cfg=cfg, traffic=traffic, peaks=peaks)
        self.model = load_model(cell["config"])
        self.gen = BatchGen(cfg, traffic["ids"], seed,
                            keep=2 * traffic["checkpoint"]["interval_batches"])
        self.spans = Spans()
        self.store_root = os.path.join(WORK, "store")
        self.trace_dir = os.path.join(WORK, "trace")
        for d in (self.store_root, self.trace_dir):
            shutil.rmtree(d, ignore_errors=True)
        self.store = timed_store(self.store_root, self.spans)
        self.ckpt = checkpoint_config(traffic)
        self.bundle, self.base = build_bundle(cfg, seed, mesh)
        self.Trainer = make_trainer_class(self.spans)
        self.sampler = Sampler(self.bundle, seed,
                               traffic["check"]["rows_per_table"],
                               self.model.table_field)
        self.checks: Dict[str, dict] = {}

    # ----------------------------------------------------------- helpers
    def trainer(self, on_boundary=None):
        from repro.train.loop import TrainerConfig

        return self.Trainer(self.bundle, self.store, self.ckpt,
                            TrainerConfig(log_every=1), batch_fn=self.gen,
                            on_boundary=on_boundary)

    @contextlib.contextmanager
    def window(self):
        """The measured window: compile counting, and the trace when one
        is asked for."""
        import jax

        self.compiles.mark()
        if self.trace:
            # device events and the harness's spans; no Python call tracing,
            # which would slow every host thread of the program
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self.spans.span("window"):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            self.rec.compiles_in_window = self.compiles.since_mark(
                BACKEND_COMPILE)
            self.rec.loads_in_window = self.compiles.since_mark(CACHE_LOAD)

    def add_check(self, name: str, value: float) -> None:
        """Compare ``value`` with its limit from the configuration's or the
        mix's ``limits``; a number they give no limit is only logged."""
        limits = {**self.cfg.get("limits", {}),
                  **self.traffic.get("limits", {})}
        if name in limits:
            self.checks[name] = {"value": value, "limit": limits[name]}
        else:
            log(f"reading {name}: {value!r} (not compared in this cell)")

    # --------------------------------------------------------------- train
    def first_steps(self, tr) -> dict:
        """Take the first three steps through the window's own call and
        feed, and read what the reference compares: each step's loss, each
        leaf's first-step gradient norm from the optimizer state (AdaGrad
        keeps g², row-wise AdaGrad the row's mean g²), and each leaf's
        change after the three, against the seed's weights drawn again."""
        import jax
        import jax.numpy as jnp

        from bench_reftrain import leaf_norms

        dims = {n: s.dim for n, s in self.bundle.tracked.items()}

        @jax.jit
        def grad_norms(opt):
            out = {f"tables/{n}": jnp.sqrt(dims[n] * jnp.sum(a))
                   for n, a in opt["tables"].items()}
            out.update(leaf_norms(jax.tree.map(jnp.sqrt, opt["dense"]),
                                  "dense"))
            return out

        init = self.base.init

        @jax.jit
        def change_norms(params, key):
            p0 = init(key)
            out = {f"tables/{n}": jnp.sqrt(jnp.sum(jnp.square(
                params["tables"][n] - p0["tables"][n])))
                for n in params["tables"]}
            out.update(leaf_norms(jax.tree.map(jnp.subtract, params["dense"],
                                               p0["dense"]), "dense"))
            return out

        tr.run(1)
        grads = {k: float(v) for k, v in
                 jax.device_get(grad_norms(tr.state.opt_state)).items()}
        tr.run(2)
        change = {k: float(v) for k, v in jax.device_get(change_norms(
            tr.state.params, jax.random.key(self.seed))).items()}
        loss = [h["loss"] for h in tr.history[:3]]
        return dict(loss=loss, grad=grads, change=change)

    def run_train(self) -> dict:
        import jax

        traffic, interval = self.traffic, self.traffic["checkpoint"][
            "interval_batches"]
        captures: Dict[int, dict] = {}
        taken: Dict[int, Any] = {}

        def on_boundary(tr):
            step = len(tr.starts) * interval + interval
            idx = self.sampler.draw(step, self.gen.recent(step - 1))
            taken[step] = (idx, self.sampler.take(tr.state, idx))

        tr = self.trainer(on_boundary)
        assert tr.init_or_restore() == 0
        prog_first = self.first_steps(tr)
        tr.cfg.log_every = 1 << 40          # no metric read in the window
        tr.run(traffic["setup_saves"] * interval - 3)
        tr.manager.wait()
        if traffic["checkpoint"]["policy"] != "full_only":
            warm_buckets(traffic, [s.dim for s in self.bundle.tracked.values()])
        jax.block_until_ready(tr.state)
        n_setup = len(tr.starts)
        taken.clear()
        self.rec.setup_s = process_age_s()

        steps0 = int(jax.device_get(tr.state.step))
        with self.window():
            t0 = time.monotonic()
            intervals = 0
            while True:
                with self.spans.span("interval"):
                    tr.run(interval)
                intervals += 1
                if time.monotonic() - t0 >= self.seconds:
                    break
            jax.block_until_ready(tr.state)
            t1 = time.monotonic()
            try:
                tr.manager.wait()
            except Exception as e:  # a save that raised did not commit
                log(f"save failed: {e!r}")
        for step, (idx, arrs) in taken.items():
            captures[step] = dict(idx=idx, **self.sampler.fetch(arrs))
        self.rec.window_s = t1 - t0
        self.rec.steps = intervals * interval
        self.rec.samples = self.rec.steps * self.cfg["batch"]
        win_starts = tr.starts[n_setup:]
        win_stalls = tr.stalls[n_setup:]
        steps = [steps0 + (k + 1) * interval for k in range(len(win_starts))]
        from bench_ref import StoreView
        view = StoreView(self.store_root)
        committed = set(view.steps())
        for s, start, stall in zip(steps, win_starts, win_stalls):
            if s not in committed:
                self.rec.failed += 1
                continue
            man = view.manifest(s)
            chunks = [(ch["n_rows"], rec_["dim"], rec_["bits"],
                       ch["sections"]["codes"][1])
                      for rec_ in man["tables"].values()
                      for ch in rec_["chunks"] if "codes" in ch["sections"]]
            rows_by_dim: Dict[int, int] = {}
            for n_rows, dim, _, _ in chunks:
                rows_by_dim[dim] = rows_by_dim.get(dim, 0) + n_rows
            self.rec.saves.append(dict(
                step=s, kind=man["kind"], start=start, stall_s=stall,
                durable_s=self.store.commits[s] - start,
                nbytes=man["nbytes_total"], wall_time_s=man["wall_time_s"],
                chunks=chunks, rows_by_dim=rows_by_dim,
                interval_samples=interval * self.cfg["batch"]))
        self.rec.attempted = len(steps)
        if self.rec.saves:
            log("window: {} saves; mean stall {:.3f} s, write wall {:.3f} s, "
                "durable {:.3f} s".format(len(self.rec.saves), *(
                    float(np.mean([s_[k] for s_ in self.rec.saves]))
                    for k in ("stall_s", "wall_time_s", "durable_s"))))
        peaks = memory_peaks(self.devices)
        tr.state = None
        tr.close()
        del tr, taken
        gc.collect()
        t = time.monotonic()
        self.check_saves(view, steps, captures)
        self.check_first_steps(prog_first)
        log(f"the check took {time.monotonic() - t:.1f} s after the window")
        return dict(mem_peaks=peaks)

    def check_saves(self, view, steps: List[int], captures: dict) -> None:
        """Compare the saves of the window with what they should hold: a
        sample of them drawn from the seed, the last always among them."""
        from bench_ref import (chunk_hash32, chunk_row_ids, decode_rows,
                               locate, primary_section, section,
                               stored_error, words_checksum)

        committed = [s for s in steps if s in set(view.steps())]
        rng = np.random.default_rng([self.seed, 0xC4])
        k = self.traffic["check"]["saves_sampled"]
        pick = sorted(set(rng.choice(committed[:-1], min(k - 1, len(
            committed) - 1), replace=False).tolist()) | {committed[-1]}) \
            if committed else []
        q = self.traffic["checkpoint"]["quant"]
        interval = self.traffic["checkpoint"]["interval_batches"]
        e_prog = e_ref = 0.0
        sel_bad = hash_bad = row_bad = dense_bad = 0
        for s in pick:
            man, cap = view.manifest(s), captures[s]
            full = man["kind"] == "full"
            want = None if full else self.gen.touched(s - interval, s)
            for name, rec_ in man["tables"].items():
                stored = []
                wanted_rows = cap["idx"][name]
                for ch in rec_["chunks"]:
                    data = view.blob(ch["key"])
                    if chunk_hash32(section(data, ch, primary_section(ch))) \
                            != ch["hash32"]:
                        hash_bad += 1
                    ids = chunk_row_ids(ch, data)
                    stored.append(ids)
                    sel, local = locate(ids, wanted_rows)
                    if not len(sel):
                        continue
                    vals, _, aux = decode_rows(rec_, ch, data, local)
                    true = cap["vals"][name][sel]
                    e_prog += float(np.sum(np.square(true - vals)))
                    e_ref += float(np.sum(stored_error(
                        true, q["bits"], q["method"], q.get("num_bins"),
                        q.get("ratio"), rec_["meta_dtype"])))
                    if "opt_acc" in aux:
                        row_bad += int(np.sum(
                            aux["opt_acc"].view(np.uint32)
                            != cap["acc"][name][sel].view(np.uint32)))
                got = np.concatenate(stored) if stored else np.zeros(0)
                exp = (np.arange(rec_["rows"]) if full
                       else want[self.model.table_field(name)])
                sel_bad += len(np.setxor1d(got, exp)) + (len(got) - len(
                    np.unique(got)))
            blobs = sorted(words_checksum(view.blob(d["key"]))
                           for k_, d in man["dense"].items()
                           if k_ not in ("step", "rng"))
            dense_bad += sum(a != b for a, b in zip(blobs, cap["dense"])) \
                + abs(len(blobs) - len(cap["dense"]))
        self.add_check("quant_excess",
                       e_prog / e_ref - 1.0 if e_ref > 0 else float("inf"))
        self.add_check("selection_mismatch", sel_bad)
        self.add_check("hash_mismatch", hash_bad)
        self.add_check("rowstate_mismatch", row_bad)
        self.add_check("dense_mismatch", dense_bad)

    def check_first_steps(self, prog: dict) -> None:
        from bench_reftrain import reference_train, training_gaps

        batches = [self.gen.make(i) for i in range(3)]
        ref = reference_train(self.model, self.cfg, self.seed, batches)
        gaps = training_gaps(prog, ref)
        for k in ("loss_gap", "grad_gap", "update_gap"):
            self.add_check(k, gaps[k])
        if gaps["excluded"]:
            log(f"leaves left out of update_gap: {gaps['excluded']}")

    # -------------------------------------------------------------- resume
    def run_resume(self) -> dict:
        import jax

        interval = self.traffic["checkpoint"]["interval_batches"]
        n_saves = self.traffic["chain_saves"]
        tr = self.trainer()
        tr.cfg.log_every = 1 << 40
        assert tr.init_or_restore() == 0
        tr.run(n_saves * interval)
        tr.manager.wait()
        last = n_saves * interval
        idx = self.sampler.draw(last, self.gen.recent(last - 1))
        live = self.sampler.fetch(self.sampler.take(tr.state, idx))
        tr.state = None
        tr.close()
        del tr
        gc.collect()

        def resume_once(record: Optional[list]):
            t0 = time.monotonic()
            with self.spans.span("resume"):
                t = self.trainer()
                start = t.init_or_restore()
                jax.block_until_ready(t.state)
            t1 = time.monotonic()
            got = self.sampler.fetch(self.sampler.take(t.state, idx))
            t.state = None
            t.close()
            if record is not None:
                record.append(dict(resume_s=t1 - t0, start=start, got=got))

        with timed_placement(self.spans):
            resume_once(None)               # warm-up: every chunk shape
            self.rec.setup_s = process_age_s()
            n_warm_restore = len(self.spans.records.get("restore", []))
            n_warm_place = len(self.spans.records.get("place", []))
            done: List[dict] = []
            with self.window():
                t0 = time.monotonic()
                while time.monotonic() - t0 < self.seconds:
                    self.rec.attempted += 1
                    try:
                        resume_once(done)
                    except Exception as e:
                        self.rec.failed += 1
                        log(f"restore failed: {e!r}")
                self.rec.window_s = time.monotonic() - t0
        host = [b - a for a, b in self.spans.records["restore"][
            n_warm_restore:]]
        place = [b - a for a, b in self.spans.records["place"][n_warm_place:]]
        for d, h, p_ in zip(done, host, place):
            self.rec.restores.append(dict(resume_s=d["resume_s"],
                                          host_s=h, place_s=p_))
        peaks = memory_peaks(self.devices)
        gc.collect()
        t = time.monotonic()
        self.check_restores(idx, live, done, last)
        log(f"the check took {time.monotonic() - t:.1f} s after the window")
        return dict(mem_peaks=peaks)

    def check_restores(self, idx, live, done, step) -> None:
        """Each restore of the window against the chain replayed by the
        reference decoder (sampled rows of every table) and against the
        live state the chain was written from (row state, dense state)."""
        from bench_ref import StoreView, replay_rows

        view = StoreView(self.store_root)
        gap = 0.0
        row_bad = dense_bad = 0
        for name, rows in idx.items():
            vals, scale, aux = replay_rows(view, step, name, rows)
            tiny = np.maximum(scale, np.float32(1e-30))[:, None]
            for d in done:
                got = d["got"]
                gap = max(gap, float(np.max(np.abs(got["vals"][name] - vals)
                                            / tiny)))
                want_acc = live["acc"][name]
                row_bad += int(np.sum(got["acc"][name].view(np.uint32)
                                      != want_acc.view(np.uint32)))
                if "opt_acc" in aux:
                    row_bad += int(np.sum(aux["opt_acc"].view(np.uint32)
                                          != want_acc.view(np.uint32)))
        for d in done:
            dense_bad += sum(a != b for a, b in zip(d["got"]["dense"],
                                                    live["dense"]))
            dense_bad += int(d["start"] != step)
        self.add_check("restore_gap", gap)
        self.add_check("rowstate_mismatch", row_bad)
        self.add_check("dense_mismatch", dense_bad)

    # ------------------------------------------------------------- execute
    def execute(self) -> dict:
        mode = self.traffic["mode"]
        try:
            out = {"train": self.run_train,
                   "resume": self.run_resume}[mode]()
        finally:
            shutil.rmtree(self.store_root, ignore_errors=True)
        if self.trace:
            from bench_trace import read_xplane, summarize
            t = self.rec.trace = summarize(read_xplane(self.trace_dir))
            log(f"trace: {t.n_chips} chip(s) with ops, busy {t.busy_s:.3f} s "
                f"of {t.window_s:.3f} s")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return out


def end_to_end(rec: RunRecord, name: str) -> Optional[float]:
    """The harness's own end-to-end metrics, by the host clock."""
    if name == "setup_s":
        return rec.setup_s
    if name == "train_samples_per_s":
        return rec.samples / rec.window_s
    if name == "durable_s" and rec.saves:
        return float(np.mean([s["durable_s"] for s in rec.saves]))
    if name == "ckpt_bytes_per_sample" and rec.saves:
        return (sum(s["nbytes"] for s in rec.saves)
                / sum(s["interval_samples"] for s in rec.saves))
    if name == "resume_s" and rec.restores:
        return float(np.mean([r["resume_s"] for r in rec.restores]))
    return None


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             bench: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line's dict. Without a TPU
    (or with fewer chips than the cell asks for) it raises SystemExit
    before anything runs, unless ``require_tpu`` is off (CPU tests, which
    also pass ``overrides``: {"config": {...}, "traffic": {...}} keys
    replacing those of the two files, for a tiny size)."""
    import jax

    from bench_yardstick import peaks_for

    bench = bench or load_bench()
    cell = find(bench["workloads"], workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    cfg.update((overrides or {}).get("config", {}))
    traffic.update((overrides or {}).get("traffic", {}))
    mesh_spec = cfg["program"].get("mesh")
    if mesh_spec and math.prod(mesh_spec["shape"]) != cell["chips"]:
        raise SystemExit(f"configuration {cell['config']!r} names a mesh of "
                         f"{mesh_spec['shape']} devices; workload "
                         f"{workload!r} asks for {cell['chips']} chip(s)")
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(f"needs {cell['chips']} TPU chip(s); JAX sees "
                         f"{len(devices)} {dev.platform} device(s)")
    devices = devices[:cell["chips"]]
    peaks = peaks_for(dev.device_kind) if require_tpu else \
        peaks_for("TPU v5 lite")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # keep every program, the quick ones too: each run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    mesh = None
    if mesh_spec:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(mesh_spec["shape"], mesh_spec["axes"], devices)
    run = Run(cell, cfg, traffic, seed, seconds, trace, peaks, compiles,
              devices, mesh)
    out = run.execute()
    rec = run.rec
    e2e, per = cell_metrics(bench, workload)
    metrics = {}
    if trace:
        for m in per:
            v = load_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            v = end_to_end(rec, m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"compiles in the window: {len(rec.compiles_in_window)} "
        f"{rec.compiles_in_window}; compile-cache loads in the window: "
        f"{len(rec.loads_in_window)} {rec.loads_in_window}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": max(out["mem_peaks"]),
              "memory_peak_bytes_by_chip": out["mem_peaks"]}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in run.checks.values()),
              "attempted": rec.attempted, "failed": rec.failed,
              "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        from bench_trace import top
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": top(rec.trace.module_s),
                               "idle_gaps": top(rec.trace.gaps_by_span)}
    result["correct"] = result["correct"] and rec.failed == 0
    result["checks"] = run.checks
    return result
