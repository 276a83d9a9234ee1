"""Guest tasks: the "unikernel applications" of this framework.

Tasks are written against the FunkyCL API only — they never touch JAX devices
directly.  They are *step-wise resumable*: ``setup()`` builds programs and
buffers (or re-attaches after restore), ``step()`` performs one preemptible
unit of work.  The runtime's driver thread calls ``step()`` in a loop; all
orchestration (evict/resume/migrate/checkpoint) lands between steps plus a
monitor-level SYNC — exactly the paper's request-boundary preemption model.

``TrainTask`` uses the *chunked* train functions (paper §3.4 data splitting):
one logical optimizer step = K microbatch EXECUTE requests + one apply
EXECUTE, so preemption waits at most one microbatch (Fig 9).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch
from repro.configs.base import ShapeConfig
from repro.core.guest import FunkyCL
from repro.core.programs import Program
from repro.core.state import GuestState
from repro.train import OptConfig, make_batch, make_chunked_train_fns
from repro.train.optimizer import init_opt_state


@dataclass
class TaskImage:
    """The "OCI image" of a task: guest binary + config (+ bitstreams)."""

    name: str
    kind: str                       # train | serve | engine-serve
    arch: str = "yi-9b-smoke"
    seq_len: int = 32
    global_batch: int = 4
    total_steps: int = 8
    chunks: int = 2                 # microbatches per step (request splitting)
    tokens_per_step: int = 4        # serve: decode tokens per step() call
    prompt_len: int = 16
    max_new_tokens: int = 8         # engine-serve: per-request cap
    # engine-serve paged KV memory (None/() keep the engine defaults)
    paged_kv: bool = True
    page_size: int = 8
    kv_pool_pages: Optional[int] = None
    kv_reserve_pages: int = 1
    prompt_buckets: tuple = ()      # e.g. (8, 16, 32); empty = (prompt_len,)
    # engine-serve disaggregation role (mixed | prefill | decode)
    role: str = "mixed"
    # engine-serve speculative decode (0 = off)
    spec_k: int = 0
    spec_draft_arch: Optional[str] = None   # None = self-draft (target arch)
    spec_draft_seed: Optional[int] = None   # None = engine seed
    spec_dynamic_k: bool = False    # adapt lookahead from live accept rate
    seed: int = 0
    opt: OptConfig = field(default_factory=lambda: OptConfig(
        warmup_steps=2, decay_steps=100))

    def instantiate(self) -> "GuestTask":
        if self.kind == "train":
            return TrainTask(self)
        if self.kind == "serve":
            return ServeTask(self)
        if self.kind == "engine-serve":
            return EngineServeTask(self)
        raise ValueError(self.kind)


class GuestTask:
    image: TaskImage

    def setup(self, cl: FunkyCL, gs: GuestState, restore: bool) -> None:
        raise NotImplementedError

    def step(self, cl: FunkyCL, gs: GuestState) -> bool:
        """One preemptible unit of work; returns True when finished."""
        raise NotImplementedError

    def teardown(self, cl: FunkyCL, gs: GuestState) -> None:
        pass

    def on_update(self, vfpga_num: int) -> None:
        """Vertical-scaling hook (paper `update` command)."""

    def on_kill(self) -> None:
        """Forced-removal hook (scale-in / node drain): release any work
        the task holds that outlives it (e.g. requeue in-flight requests)."""

    def drain(self) -> None:
        """Graceful-decommission hook: stop taking new work and finish what
        is already held.  Tasks without a notion of draining ignore it."""

    @property
    def drained(self) -> bool:
        """True once a draining task holds no unfinished work."""
        return True

    def program_ids(self) -> tuple:
        """Program ("bitstream") ids this guest compiles — the placement
        layer matches them against node program caches for warm-cache
        affinity.  Empty means unknown (e.g. before setup)."""
        return ()


class TrainTask(GuestTask):
    PROGRAMS = ("init_state", "grad_init", "grad_step", "apply")

    def __init__(self, image: TaskImage):
        self.image = image
        self.cfg = get_arch(image.arch)
        self.shape = ShapeConfig("task", "train", image.seq_len,
                                 image.global_batch)

    def program_ids(self) -> tuple:
        return self.PROGRAMS

    # -- programs -------------------------------------------------------------
    def _build_programs(self):
        from repro.models import build_model

        bundle = build_model(self.cfg)
        oc = self.image.opt
        grad_init, grad_step, apply_step = make_chunked_train_fns(bundle, oc)

        def init_state(seed):
            params = bundle.init(jax.random.PRNGKey(seed))
            return params, init_opt_state(oc, params)

        def apply_fn(params, opt_state, grad_acc):
            p, o, stats = apply_step(params, opt_state, grad_acc,
                                     self.image.chunks)
            return p, o, stats["grad_norm"]

        self._bundle = bundle
        self._progs = {
            "init_state": Program("init_state", init_state),
            "grad_init": Program("grad_init", grad_init),
            "grad_step": Program("grad_step", grad_step),
            "apply": Program("apply", apply_fn),
        }

    def _abstracts(self):
        p_abs = jax.eval_shape(lambda: self._progs["init_state"].fn(0))
        params_abs, opt_abs = p_abs
        grad_abs = jax.eval_shape(self._progs["grad_init"].fn, params_abs)
        mb = make_batch(self.cfg, self.shape, 0)
        mb_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // self.image.chunks,) + x.shape[1:], x.dtype), mb)
        return params_abs, opt_abs, grad_abs, mb_abs

    def setup(self, cl: FunkyCL, gs: GuestState, restore: bool) -> None:
        self._build_programs()
        params_abs, opt_abs, grad_abs, mb_abs = self._abstracts()
        # clCreateProgramWithBinary -> vfpga_init + reconfiguration
        cl.clCreateProgramWithBinary(self._progs["init_state"], (0,))
        cl.clCreateProgramWithBinary(self._progs["grad_init"], (params_abs,))
        cl.clCreateProgramWithBinary(
            self._progs["grad_step"], (params_abs, grad_abs, mb_abs))
        cl.clCreateProgramWithBinary(
            self._progs["apply"], (params_abs, opt_abs, grad_abs))
        if not restore:
            cl.clCreateBuffer("params", params_abs)
            cl.clCreateBuffer("opt_state", opt_abs)
            cl.clCreateBuffer("grad_acc", grad_abs)
            cl.clCreateBuffer("batch", mb_abs)
            cl.clCreateBuffer("loss", jax.ShapeDtypeStruct((), jnp.float32))
            cl.clCreateBuffer("grad_norm", jax.ShapeDtypeStruct((), jnp.float32))
            cl.clEnqueueKernel("init_state", (), ("params", "opt_state"),
                               const_args=(self.image.seed,))
            cl.clFinish()

    def step(self, cl: FunkyCL, gs: GuestState) -> bool:
        """One *chunk* of a logical optimizer step (paper §3.4 splitting).

        Each driver-loop iteration submits exactly one microbatch EXECUTE, so
        preemption waits at most one chunk — and a task evicted mid-
        accumulation resumes bit-exactly: ``chunk_idx`` lives in the guest
        (VM) state and ``grad_acc`` is a DIRTY tracked buffer.
        """
        k = self.image.chunks
        ci = gs.user.get("chunk_idx", 0)
        if ci == 0:
            cl.clEnqueueKernel("grad_init", ("params",), ("grad_acc",))
        full = make_batch(self.cfg, self.shape, gs.step,
                          batch_override=self.image.global_batch)
        mb_size = self.image.global_batch // k
        mb = jax.tree.map(
            lambda x: x[ci * mb_size:(ci + 1) * mb_size], full)
        cl.write_buffer("batch", mb)
        cl.clEnqueueKernel("grad_step", ("params", "grad_acc", "batch"),
                           ("grad_acc", "loss"))
        if ci + 1 < k:
            cl.clFinish()
            gs.user["chunk_idx"] = ci + 1
            return False
        cl.clEnqueueKernel("apply", ("params", "opt_state", "grad_acc"),
                           ("params", "opt_state", "grad_norm"))
        cl.clFinish()
        gs.user["chunk_idx"] = 0
        gs.step += 1
        gs.data_position = gs.step
        return gs.step >= self.image.total_steps

    def teardown(self, cl: FunkyCL, gs: GuestState) -> None:
        gs.user["final_loss"] = float(jnp.asarray(cl.read_buffer("loss")))
        # read results out before releasing: the monitor zeroes device memory
        # on vfpga_exit (paper §3.4 isolation). Host-side only; never hits a
        # JSON manifest (checkpoints only happen while RUNNING).
        gs.user["final_params"] = cl.read_buffer("params")
        for pid in ("init_state", "grad_init", "grad_step", "apply"):
            cl.clReleaseProgram(pid)


class ServeTask(GuestTask):
    """Batched greedy decoding service; one step() = tokens_per_step tokens."""

    PROGRAMS = ("init_params", "prefill", "decode")

    def __init__(self, image: TaskImage):
        self.image = image
        self.cfg = get_arch(image.arch)

    def program_ids(self) -> tuple:
        return self.PROGRAMS

    def _build_programs(self):
        from repro.models import build_model

        bundle = build_model(self.cfg)

        def init_params(seed):
            return bundle.init(jax.random.PRNGKey(seed))

        def prefill(params, tokens):
            logits, caches = bundle.prefill_fn(params, {"tokens": tokens})
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return tok, jnp.int32(tokens.shape[1]), caches

        def decode(params, token, pos, caches):
            logits, caches = bundle.decode_fn(params, token, pos, caches)
            return (jnp.argmax(logits, -1).astype(jnp.int32), pos + 1, caches)

        self._bundle = bundle
        self._progs = {
            "init_params": Program("init_params", init_params),
            "prefill": Program("prefill", prefill),
            "decode": Program("decode", decode),
        }

    def setup(self, cl: FunkyCL, gs: GuestState, restore: bool) -> None:
        self._build_programs()
        im = self.image
        params_abs = jax.eval_shape(lambda: self._progs["init_params"].fn(0))
        toks_abs = jax.ShapeDtypeStruct((im.global_batch, im.prompt_len),
                                        jnp.int32)
        pre_abs = jax.eval_shape(self._progs["prefill"].fn, params_abs,
                                 toks_abs)
        tok_abs, pos_abs, caches_abs = pre_abs
        cl.clCreateProgramWithBinary(self._progs["init_params"], (0,))
        cl.clCreateProgramWithBinary(self._progs["prefill"],
                                     (params_abs, toks_abs))
        cl.clCreateProgramWithBinary(
            self._progs["decode"], (params_abs, tok_abs, pos_abs, caches_abs))
        if not restore:
            cl.clCreateBuffer("params", params_abs)
            cl.clCreateBuffer("prompt", toks_abs)
            cl.clCreateBuffer("token", tok_abs)
            cl.clCreateBuffer("pos", pos_abs)
            cl.clCreateBuffer("caches", caches_abs)
            cl.clEnqueueKernel("init_params", (), ("params",),
                               const_args=(im.seed,))
            prompt = make_batch(self.cfg,
                                ShapeConfig("p", "train", im.prompt_len,
                                            im.global_batch), 0)["tokens"]
            cl.write_buffer("prompt", prompt)
            cl.clEnqueueKernel("prefill", ("params", "prompt"),
                               ("token", "pos", "caches"))
            cl.clFinish()

    def step(self, cl: FunkyCL, gs: GuestState) -> bool:
        for _ in range(self.image.tokens_per_step):
            cl.clEnqueueKernel("decode", ("params", "token", "pos", "caches"),
                               ("token", "pos", "caches"))
        cl.clFinish()
        gs.step += 1
        return gs.step >= self.image.total_steps

    def teardown(self, cl: FunkyCL, gs: GuestState) -> None:
        gs.user["last_token"] = cl.read_buffer("token").tolist()
        for pid in ("init_params", "prefill", "decode"):
            cl.clReleaseProgram(pid)


class EngineServeTask(GuestTask):
    """Per-request serving replica: a continuous-batching engine pulling
    admissible requests from the service's ``RequestRouter`` and pushing
    engine-reported completions back.

    One ``step()`` = one engine iteration (admissions + one vmapped decode
    EXECUTE), so orchestration commands land between iterations and the
    whole in-flight batch is preemptible at token boundaries.  The task
    finishes when the router is closed and every lane has drained; a
    replicate-clone starts with empty lanes (the source keeps its own
    in-flight sequences) and immediately joins the admission pool.

    ``drain()`` puts the replica into a *draining* state: it stops pulling
    admissions from the router and finishes the sequences it already holds,
    so request-boundary scale-in decommissions the replica without
    requeueing (and recomputing) in-flight work.
    """

    def __init__(self, image: TaskImage):
        self.image = image
        self._engine = None
        self._draining = False

    def setup(self, cl: FunkyCL, gs: GuestState, restore: bool) -> None:
        from repro.scaling.serving import get_router
        from repro.serve.engine import ContinuousBatchingEngine, SpecConfig

        if restore and self._engine is not None:
            # migrated with its context: in-flight lanes resume mid-decode
            self._engine.reattach(cl)
            return
        im = self.image
        self._router = get_router(im.name,
                                  registry=cl._monitor.telemetry)
        spec = (SpecConfig(k=im.spec_k, draft_arch=im.spec_draft_arch,
                           draft_seed=im.spec_draft_seed,
                           dynamic_k=im.spec_dynamic_k)
                if im.spec_k > 0 else None)
        self._engine = ContinuousBatchingEngine(
            im.arch, cl, slots=im.global_batch, prompt_len=im.prompt_len,
            max_new_tokens=im.max_new_tokens, service=im.name,
            engine_id=cl._monitor.task_id, seed=im.seed,
            paged=im.paged_kv, page_size=im.page_size,
            pool_pages=im.kv_pool_pages,
            reserve_pages=im.kv_reserve_pages,
            prompt_buckets=im.prompt_buckets or None, spec=spec,
            role=im.role)
        self._engine.setup(restore=restore)

    def step(self, cl: FunkyCL, gs: GuestState) -> bool:
        moved = self._engine.pump(self._router, admit=not self._draining)
        gs.step += 1
        if self._draining and self._engine.idle:
            return True                  # drained: exit at request boundary
        if not moved:
            if self._router.closed:
                return True
            time.sleep(0.002)            # idle poll; don't spin the monitor
        return gs.step >= self.image.total_steps

    def drain(self) -> None:
        self._draining = True

    @property
    def drained(self) -> bool:
        return self._engine is None or self._engine.idle

    def program_ids(self) -> tuple:
        return self._engine.program_ids() if self._engine is not None else ()

    def teardown(self, cl: FunkyCL, gs: GuestState) -> None:
        gs.user["completed"] = len(self._engine.completed)
        for pid in self._engine.program_ids():
            cl.clReleaseProgram(pid)

    def on_kill(self) -> None:
        # scale-in removed this replica: report anything already finished,
        # then hand un-finished sequences back to the router so another
        # replica re-serves them (greedy decode is deterministic — the
        # client sees the same tokens again)
        if self._engine is None:
            return
        for rec in self._engine.drain_completions():
            self._router.complete(rec)
        reqs = self._engine.evacuate()
        if reqs:
            self._router.requeue(reqs)
