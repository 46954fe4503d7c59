"""Training a decoder with ``launch/train.py``'s parts.

The architecture comes from the configuration: the stem of its
``"reference"`` names ``perfbench/archs/<stem>.py``, which gives the
program's ``ModelConfig``, the parameter tree, the plain reference and the
step's FLOPs (see ``archs/llama.py``).

Set-up builds one object, the compiled step (``make_train_step`` under
``jax.jit`` with the state donated, as ``launch/train.py`` builds it) with
its state: weights from the seed, made on the device in one jitted call;
AdamW's state with float32 masters. The feed is ``DataPipeline`` over a
``TokenStore`` holding a seeded Zipf corpus. Set-up drives that object
through its first three steps, through the window's own call and feed,
and records what the comparison needs: the losses, the per-leaf norms of
the first gradient as the optimizer got it (its first moment over
1 - beta1), and of the masters' change over the three steps. The window
continues from step four, dispatching steps without waiting on them, and
``train_tok_s`` is every step's tokens over the time until the last one
finished.
"""
from __future__ import annotations

import functools
import importlib
import time
from pathlib import Path
from typing import Dict, FrozenSet, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..lib import hlo_scopes, lm_data, xtrace
from ..lib.harness import BENCH_DIR, Check, Window, log

CHECK_STEPS = 3
# steps the host may dispatch before the oldest of them has finished
AHEAD = 2


def architecture(cfg: Dict):
    """The module of ``perfbench/archs/`` named by the stem of the
    configuration's ``"reference"``."""
    stem = Path(cfg["reference"]).stem
    if not (BENCH_DIR / "archs" / f"{stem}.py").is_file():
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} names the reference "
            f"{cfg['reference']!r}, and there is no "
            f"{BENCH_DIR / 'archs' / (stem + '.py')}")
    return importlib.import_module(f"..archs.{stem}", __package__)


def leaf_norms(flat: Dict[str, jax.Array],
               stacked: FrozenSet[str]) -> Dict[str, jax.Array]:
    """Norm of every leaf, each layer of a ``stacked`` leaf on its own."""
    out = {}
    for path, x in flat.items():
        x = x.astype(jnp.float32)
        if path in stacked:
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"{path}[{i}]"] = n[i]
        else:
            out[path] = jnp.sqrt(jnp.sum(x * x))
    return out


_norms = jax.jit(leaf_norms, static_argnums=1)


@functools.partial(jax.jit, static_argnums=2)
def _change_norms(master: Dict, init: Dict, stacked: FrozenSet[str]) -> Dict:
    return leaf_norms({k: master[k] - init[k].astype(jnp.float32)
                       for k in master}, stacked)


@jax.jit
def _fresh_opt_state(params: Dict) -> Dict:
    """AdamW's state before the first step: zero moments, float32 masters
    (new buffers: the step donates them)."""
    def zeros(p):
        return jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "master": jax.tree.map(lambda p: p.astype(jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              leave_out=()) -> Dict[str, float]:
    """Every leaf's |norm(got) - norm(want)|, over the larger of that
    leaf's reference norm and the median leaf's."""
    keys = [k for k in want if k not in leave_out]
    med = float(np.median([want[k] for k in keys]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keys}


def norm_gap(got: Dict[str, float], want: Dict[str, float],
             leave_out=()) -> float:
    """The worst leaf's gap (``leaf_gaps``)."""
    return max(leaf_gaps(got, want, leave_out).values())


def readings(got, want) -> Dict[str, float]:
    """The compared numbers of one run of the first steps (``got``) against
    the reference's (``want``), each a (losses, first-gradient norms,
    change norms) triple. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam and
    are left out of the change."""
    med = float(np.median(list(want[1].values())))
    still = {k for k, v in want[1].items() if v < 1e-3 * med}
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got[0], want[0])),
        "grad_gap": norm_gap(got[1], want[1]),
        "change_gap": norm_gap(got[2], want[2], still),
    }


class Cell:
    def __init__(self, config: Dict, traffic: Dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        self.arch = architecture(config)
        self.input_s: List[float] = []
        self.window_metrics: List[Dict[str, jax.Array]] = []
        self._split = None

    def _params(self) -> Dict:
        return lm_data.make_params(self.arch.param_shapes(self.config),
                                   self.config["torch_dtype"], self.seed)

    # ------------------------------------------------------------ set-up ----
    def setup(self) -> None:
        from repro.data import DataPipeline, PipelineConfig, TokenStore
        from repro.launch.mesh import make_device_mesh
        from repro.models import build_model
        from repro.optim import AdamWConfig, adamw_init_specs
        from repro.train import make_plan, make_train_step, use_plan
        from repro.train.sharding import resolve_shardings

        cfg, tr = self.config, self.traffic
        t0 = time.perf_counter()
        self.mcfg = self.arch.model_config(cfg)
        model = build_model(self.mcfg)
        specs = model.specs()
        self.stacked = frozenset(
            path for path, spec in lm_data.flatten(specs).items()
            if spec.axes[:1] == ("layers",))
        self.mesh = make_device_mesh(jax.devices()[:1])
        self.plan = make_plan(self.mesh)
        params = self._params()
        want = jax.tree.map(lambda s: (tuple(s.shape), s.dtype), specs,
                            is_leaf=lambda s: hasattr(s, "axes"))
        got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
        if got != want:
            raise ValueError(f"the program's parameter tree is not the "
                             f"benchmark's: {want} != {got}")
        self.use_plan = use_plan
        params = jax.device_put(params, resolve_shardings(specs, self.plan))
        opt = jax.device_put(_fresh_opt_state(params), resolve_shardings(adamw_init_specs(specs),
                                                    self.plan))
        self.opt_cfg = AdamWConfig(**cfg["optimizer"])
        self.step_fn = jax.jit(make_train_step(model, self.mcfg, self.opt_cfg),
                               donate_argnums=(0, 1))
        t1 = time.perf_counter()
        doc_len = self.seq + 1 + tr["doc_pad"]
        self.corpus = lm_data.zipf_tokens(self.seed, (tr["docs"], doc_len),
                                          cfg["vocab_size"])
        store = TokenStore(tr["docs"], doc_len,
                           cuboid=(tr["doc_cuboid"], min(4096, doc_len)))
        store.ingest_corpus(self.corpus)
        self.pipe = DataPipeline(store, PipelineConfig(
            seq_len=self.seq, global_batch=self.batch,
            seed=self._pipeline_seed(store)))
        t2 = time.perf_counter()
        # the first steps, through the window's own call and feed
        self.fed: List[Dict[str, np.ndarray]] = []
        self.losses = []
        state = (params, opt)
        for s in range(CHECK_STEPS):
            state, metrics = self._step(state, s, record=True)
            self.losses.append(metrics["loss"])
            if s == 0:
                b1 = self.opt_cfg.b1
                self.grad_norms = _norms({
                    k: v / (1 - b1)
                    for k, v in lm_data.flatten(state[1]["mu"]).items()},
                    self.stacked)
        init = lm_data.flatten(self._params())
        self.change_norms = _change_norms(
            lm_data.flatten(state[1]["master"]), init, self.stacked)
        del init
        self.losses = [float(x) for x in self.losses]
        self.grad_norms = {k: float(v) for k, v in self.grad_norms.items()}
        self.change_norms = {k: float(v)
                             for k, v in self.change_norms.items()}
        self.state = state
        self.next_step = CHECK_STEPS
        log(f"lm_train set-up: model and step {t1 - t0:.2f} s, corpus "
            f"{t2 - t1:.2f} s, first {CHECK_STEPS} steps "
            f"{time.perf_counter() - t2:.2f} s; losses {self.losses}")

    def _pipeline_seed(self, store) -> int:
        """The feed's seed: the first drawn from the run's seed under which
        the checked steps' rows all differ."""
        from repro.data import DataPipeline, PipelineConfig
        for k in range(64):
            seed = int(np.random.SeedSequence([self.seed, 2, k])
                       .generate_state(1)[0])
            pipe = DataPipeline(store, PipelineConfig(
                seq_len=self.seq, global_batch=self.batch, seed=seed))
            rows = np.concatenate([pipe.host_slice(s)
                                   for s in range(CHECK_STEPS)])
            if len(np.unique(rows)) == len(rows):
                return seed
        raise RuntimeError("no feed seed gives distinct rows")

    def _step(self, state, step: int, record: bool = False):
        from jax.sharding import NamedSharding
        from repro.train import batch_pspec
        t0 = time.perf_counter()
        with xtrace.span("input"):
            batch = self.pipe.get_batch(step)
            if record:
                self.fed.append({k: v.copy() for k, v in batch.items()})
            batch = {k: jax.device_put(v, NamedSharding(
                self.mesh, batch_pspec(self.plan, v.ndim, v.shape[0])))
                for k, v in batch.items()}
        self.input_s.append(time.perf_counter() - t0)
        if step == CHECK_STEPS - 1:     # arguments shaped as the window's
            self.step_args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                (*state, batch))
        with xtrace.span("step"), self.use_plan(self.plan):
            params, opt, metrics = self.step_fn(*state, batch)
        return (params, opt), metrics

    # ------------------------------------------------------------ window ----
    def window(self, seconds: float, traced: bool) -> Window:
        self.input_s.clear()
        state, n, pending, done = self.state, 0, [], []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            state, metrics = self._step(state, self.next_step + n)
            self.window_metrics.append(metrics)
            n += 1
            # the host runs at most AHEAD steps in front of the device
            pending.append(metrics["loss"])
            if len(pending) > AHEAD:
                with xtrace.span("wait"):
                    pending.pop(0).block_until_ready()
                done.append(time.perf_counter())
        with xtrace.span("drain"):
            jax.block_until_ready((state, metrics))
        t1 = time.perf_counter()
        gaps = np.diff(done) if len(done) > 1 else np.zeros(1)
        log(f"window: {n} steps; between finished steps median "
            f"{np.median(gaps):.4f} s, longest {gaps.max():.4f} s; input "
            f"longest {max(self.input_s, default=0.0):.4f} s")
        self.state = state
        self.window_steps, self.window_s = n, t1 - t0
        self.last_loss = float(metrics["loss"])
        return Window(metrics={"train_tok_s": n * self.batch * self.seq
                               / (t1 - t0)},
                      attempted=n, failed=0 if np.isfinite(self.last_loss)
                      else 1)

    def release(self) -> None:
        self.state = None
        self.pipe.stop()

    def counters(self) -> Dict[str, float]:
        """The mean over the window's steps of each scalar the step returns
        (its loss, and any count an architecture's FLOPs depend on)."""
        got = jax.device_get(self.window_metrics)
        return {k: float(np.mean([m[k] for m in got])) for k in got[0]
                if np.ndim(got[0][k]) == 0} if got else {}

    def step_hlo(self) -> Tuple[str, str]:
        """The step's module name and compiled text: lowered again for the
        window's arguments, and taken from the compilation cache."""
        with self.use_plan(self.plan):
            text = self.step_fn.lower(*self.step_args).compile().as_text()
        return text.split(None, 2)[1].rstrip(","), text

    def layer_split(self, trace) -> Tuple[Dict[Tuple[str, str], float], int]:
        """Device seconds of the traced window by (layer, pass) (see
        ``hlo_scopes``), and the step's calls in it. Read after the window,
        so that the step's text is fetched outside set-up and window."""
        if self._split is None:
            module, text = self.step_hlo()
            ops = hlo_scopes.parse(text)
            split = hlo_scopes.split(trace.op_s, {module: ops},
                                     self.arch.SCOPES, self.arch.LAYERS)
            steps = trace.module_seconds(module)[1]
            self._split = split, steps
            mine = {k.split(":", 1)[1]: v for k, v in trace.op_s.items()
                    if k.startswith(module + ":")}
            named = sum(v for k, v in mine.items() if k in ops)
            per = max(steps, 1)
            log(f"device ms a step by layer and pass ({steps} steps of "
                f"{module}; busy {1e3 * trace.busy_s / per:.3f}; the text "
                f"names {named / max(sum(mine.values()), 1e-12):.4f} of the "
                f"step's time): " + ", ".join(
                    f"{k[0]}.{k[1]} {1e3 * v / per:.3f}"
                    for k, v in sorted(split.items())))
        return self._split

    # ------------------------------------------------------------- check ----
    def reference(self, precision: str = "f32"):
        """The reference's three steps on the rows that were fed, taken from
        the benchmark's own corpus."""
        by_row = {self.corpus[i, :self.seq + 1].tobytes(): i
                  for i in range(len(self.corpus))}
        self.feed_wrong, batches = 0, []
        for b in self.fed:
            seq = np.concatenate([b["tokens"], b["labels"][:, -1:]], 1)
            rows = [by_row.get(r.astype(np.int32).tobytes()) for r in seq]
            self.feed_wrong += sum(r is None for r in rows)
            rows = [0 if r is None else r for r in rows]
            full = self.corpus[rows, :self.seq + 1]
            batches.append((full[:, :-1], full[:, 1:]))
        params = jax.tree.map(lambda a: a.astype(jnp.float32), self._params())
        losses, grad, final = self.arch.reference.train(
            self.config, self.config["optimizer"], params, batches, precision)
        grad_n = {k: float(v) for k, v in
                  _norms(lm_data.flatten(grad), self.stacked).items()}
        change_n = {k: float(v) for k, v in _change_norms(
            lm_data.flatten(final), lm_data.flatten(params),
            self.stacked).items()}
        return losses, grad_n, change_n

    def program(self):
        """The program's own readings of its first steps, in the
        reference's form: (losses, first-gradient norms, change norms)."""
        return self.losses, self.grad_norms, self.change_norms

    def checks(self) -> List[Check]:
        limits = self.traffic["limits"]
        got = readings(self.program(), self.reference())
        return ([Check("feed_wrong", self.feed_wrong, 0),
                 Check("loss_finite", 0 if np.isfinite(self.last_loss)
                       else 1, 0)]
                + [Check(k, v, limits[k]) for k, v in got.items()])

    def calibration(self, control: bool = True) -> Dict:
        """The program's numbers and, with ``control``, the control's: the
        float8 reference in the program's place."""
        want = self.reference("f32")
        med = float(np.median(list(want[1].values())))
        still = {k for k, v in want[1].items() if v < 1e-3 * med}
        worst = {name: sorted(leaf_gaps(g, w, skip).items(),
                              key=lambda kv: -kv[1])[:3]
                 for name, g, w, skip in (("grad", self.grad_norms, want[1], ()),
                                          ("change", self.change_norms,
                                           want[2], still))}
        out = {"program": readings(self.program(), want),
               "feed_wrong": self.feed_wrong, "worst_leaves": worst,
               "losses": [self.losses, want[0]]}
        if control:
            out["control"] = readings(self.reference("fp8"), want)
        return out

    def close(self) -> None:
        pipe = getattr(self, "pipe", None)
        if pipe is not None:
            pipe.stop()
