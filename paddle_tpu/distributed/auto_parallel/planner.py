"""Cost-model-driven parallelism planner: pick (dp, pp, mp, sharding,
remat, n_micro, donation, wire dtype) for a GPT-family model on N chips
without touching a device.

ROADMAP item 2. PR 5 made "how fast/big is this program" a pure function
of (jaxpr, mesh, PartitionSpecs); PR 9 added the int8 wire what-if. This
module closes the loop: it enumerates every legal mesh factorization of
the slice, prunes infeasible candidates against ``chip_specs()`` HBM
budgets, and ranks the survivors by the SAME trace-based roofline the
bench's ``*_predicted`` rows use (:func:`paddle_tpu.analysis.passes.cost
.estimate_jaxpr_cost` + :func:`..memory.estimate_jaxpr_peak`) — one cost
model, one answer.

Search pipeline (pure planning — no device execution, no compile):

1. **enumerate** — all (dp, mp, pp, sharding) with ``dp*mp*pp*sh == N``
   x micro-batch x remat choices, filtered by model divisibility
   (heads/vocab % mp, layers % pp, batch % (n_micro*dp*sh));
2. **closed-form HBM prune** — params + Adam moments per device alone
   over the chip budget rejects the candidate before any trace (the
   PTMM001 verdict, computed in closed form: activations only add);
3. **pre-rank** — the instant closed-form roofline
   (:class:`.cost_model.CostEstimator`, same ``chip_specs()`` table)
   orders the survivors so only the ``max_traces`` most promising pay
   for a trace;
4. **trace + score** — each finalist is built as a
   ``GPTHybridTrainStep.abstract`` on a *virtual* mesh
   (``jax.sharding.AbstractMesh`` — any N on any host, no devices) and
   priced end to end: ``step_jaxpr()`` through the cost pass for the
   roofline step time / MFU (the EQuARX int8-wire what-if decides
   ``wire_dtype`` per plan), ``step_arg_divisors()`` through the
   liveness memory pass for peak HBM under donation (PTMM001 over
   budget = infeasible).

A 13B plan over 16-64 chips costs seconds. ``tools/plan.py`` is the CLI;
``Engine.prepare(plan=...)`` executes the winner;
:func:`plan_serving` runs the same search shape over the serving
engine's (concurrency-bucket, page-size, quantize) space using
``serving/predict.py`` rows.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

__all__ = ["Plan", "PlanReport", "Planner", "plan_gpt", "price_config",
           "plan_serving", "virtual_hcg", "PLANNER_MODELS"]


# named-model registry: (config factory, default global batch, seq,
# step kwargs) — the 13b entry mirrors analysis.predict.BENCH_CONFIGS
# ("13b") so planner-vs-hand comparisons price the same program family
def _model_registry():
    from ...models.gpt import (gpt_13b_config, gpt_1p3b_config,
                               gpt_345m_config, gpt_tiny_config)
    bf16 = dict(compute_dtype="bfloat16", param_dtype="bfloat16",
                moment_dtype="bfloat16")
    return {
        "gpt_tiny": (gpt_tiny_config, 8, 128,
                     dict(compute_dtype="bfloat16")),
        "gpt_345m": (lambda: gpt_345m_config(
            max_position_embeddings=1024, num_heads=8), 12, 1024,
            dict(compute_dtype="bfloat16")),
        "gpt_1p3b": (gpt_1p3b_config, 6, 2048, bf16),
        "gpt_13b": (gpt_13b_config, 16, 2048, bf16),
    }


PLANNER_MODELS = ("gpt_tiny", "gpt_345m", "gpt_1p3b", "gpt_13b")


class virtual_hcg:
    """Context manager: a HybridCommunicateGroup over an
    ``AbstractMesh`` — trace/plan any (dp, mp, pp, sharding) topology
    with zero devices attached. The global mesh/hcg the constructor
    installs are restored on exit, so planning never leaks a virtual
    topology into the caller's process state."""

    def __init__(self, dp=1, mp=1, pp=1, sharding=1):
        self.degrees = dict(dp=dp, mp=mp, pp=pp, sharding=sharding)

    def __enter__(self):
        from jax.sharding import AbstractMesh
        from .. import mesh as mesh_mod
        d = self.degrees
        self._saved = (mesh_mod._global_mesh, mesh_mod._hcg)
        am = AbstractMesh((d["pp"], d["dp"], d["sharding"], 1, d["mp"]),
                          ("pp", "dp", "sharding", "sep", "mp"))
        return mesh_mod.HybridCommunicateGroup(
            dp_degree=d["dp"], mp_degree=d["mp"], pp_degree=d["pp"],
            sharding_degree=d["sharding"], mesh=am)

    def __exit__(self, *exc):
        from .. import mesh as mesh_mod
        mesh_mod._global_mesh, mesh_mod._hcg = self._saved
        return False


@dataclass
class Plan:
    """One fully-specified parallelism configuration + its predictions.

    ``step_ms``/``predicted_mfu``/``peak_hbm_bytes`` come from the
    trace-based model when ``traced`` is True (authoritative); pruned or
    un-traced candidates carry the closed-form estimate and a
    ``reject_reason``."""

    dp: int = 1
    mp: int = 1
    pp: int = 1
    sharding: int = 1
    n_micro: int = 1
    remat: object = False          # False | "dots" | True
    pipeline_schedule: str = "gpipe"
    donate: bool = True
    wire_dtype: str | None = None  # None (native) | "int8"
    global_batch: int = 8
    seq_len: int = 1024
    chip: str = "v5e"
    n_devices: int = 1
    # predictions
    step_ms: float = 0.0
    predicted_mfu: float = 0.0
    peak_hbm_bytes: float = 0.0
    bound: str = "compute"
    compute_ms: float = 0.0
    hbm_ms: float = 0.0
    comm_ms: float = 0.0
    tokens_per_sec_per_chip: float = 0.0
    requires_donation: bool = False
    feasible: bool = True
    traced: bool = False
    reject_reason: str | None = None
    extras: dict = field(default_factory=dict)

    @property
    def mesh(self) -> str:
        return f"dp{self.dp}xmp{self.mp}xpp{self.pp}xsh{self.sharding}"

    def mesh_degrees(self) -> dict:
        return dict(dp=self.dp, mp=self.mp, pp=self.pp,
                    sharding=self.sharding)

    def as_dict(self) -> dict:
        return {
            "mesh": self.mesh, "dp": self.dp, "mp": self.mp,
            "pp": self.pp, "sharding": self.sharding,
            "n_micro": self.n_micro, "remat": str(self.remat),
            "pipeline_schedule": self.pipeline_schedule,
            "donate": self.donate, "wire_dtype": self.wire_dtype,
            "global_batch": self.global_batch, "seq_len": self.seq_len,
            "chip": self.chip, "n_devices": self.n_devices,
            "step_ms": round(self.step_ms, 3),
            "predicted_mfu": round(self.predicted_mfu, 4),
            "peak_hbm_gb": round(self.peak_hbm_bytes / 1024 ** 3, 3),
            "bound": self.bound,
            "compute_ms": round(self.compute_ms, 3),
            "hbm_ms": round(self.hbm_ms, 3),
            "comm_ms": round(self.comm_ms, 3),
            "tokens_per_sec_per_chip": round(
                self.tokens_per_sec_per_chip, 1),
            "requires_donation": self.requires_donation,
            "feasible": self.feasible, "traced": self.traced,
            "reject_reason": self.reject_reason,
        }


@dataclass
class PlanReport:
    """Ranked planner output: ``plans`` are the traced, feasible
    candidates fastest-first; ``pruned`` the rejected ones (with
    reasons); ``planner_s`` the search wall time (the bench's
    plan-time-regression signal)."""

    plans: list = field(default_factory=list)
    pruned: list = field(default_factory=list)
    planner_s: float = 0.0
    n_candidates: int = 0
    n_traced: int = 0
    model: str | None = None
    chip: str = "v5e"
    n_devices: int = 1

    @property
    def best(self) -> Plan:
        if not self.plans:
            reasons = sorted({p.reject_reason for p in self.pruned
                              if p.reject_reason})
            raise RuntimeError(
                "no feasible strategy fits chip memory "
                f"({'; '.join(reasons) or 'empty search space'}); grow "
                "the slice or enable more sharding/remat")
        return self.plans[0]

    def as_dict(self) -> dict:
        return {
            "model": self.model, "chip": self.chip,
            "n_devices": self.n_devices,
            "planner_s": round(self.planner_s, 3),
            "n_candidates": self.n_candidates,
            "n_traced": self.n_traced,
            "plans": [p.as_dict() for p in self.plans],
            "n_pruned": len(self.pruned),
        }


def _factorizations(n, ways):
    """All ordered tuples of ``ways`` ints >= 1 whose product is n."""
    if ways == 1:
        yield (n,)
        return
    for d in sorted({d for d in range(1, n + 1) if n % d == 0}):
        for rest in _factorizations(n // d, ways - 1):
            yield (d,) + rest


class Planner:
    """Search parallelism plans for ``config`` on ``n_devices`` of
    ``chip``. See the module docstring for the four-stage pipeline."""

    def __init__(self, config, n_devices, chip="v5e", global_batch=None,
                 seq_len=None, headroom=0.9, max_mp=8, max_pp=None,
                 n_micro_choices=None, remat_choices=(False, "dots", True),
                 pipeline_schedule="1f1b", wire_dtypes=(None, "int8"),
                 max_traces=8, step_kw=None, model_name=None):
        self.config = config
        self.n_devices = int(n_devices)
        # `chip` is a chip_specs() name ("v5e") or a ready spec dict
        # with the same keys (the tuner's Cluster-compat path)
        if isinstance(chip, dict):
            self.chip = dict(chip)
            self.chip_name = chip.get("name", "custom")
        else:
            from ...observability.instrument import chip_specs
            self.chip = chip_specs(chip)
            self.chip_name = chip
        self.global_batch = int(global_batch or max(self.n_devices, 8))
        self.seq_len = int(seq_len or config.max_position_embeddings)
        self.headroom = headroom
        self.hbm_budget = self.chip["hbm_gb"] * 1024 ** 3 * headroom
        self.max_mp = max_mp
        self.max_pp = max_pp or config.num_layers
        self.n_micro_choices = n_micro_choices
        self.remat_choices = tuple(remat_choices)
        self.pipeline_schedule = pipeline_schedule
        self.wire_dtypes = tuple(wire_dtypes)
        self.max_traces = int(max_traces)
        self.step_kw = dict(step_kw or {})
        self.model_name = model_name

    # -------------------------------------------------- stage 1: enumerate
    def _micro_choices(self, dp, pp, sh):
        """Micro-batch counts that divide the per-replica batch; pp > 1
        needs n_micro >= pp to fill the pipeline."""
        if self.n_micro_choices is not None:
            cand = self.n_micro_choices
        else:
            cand = sorted({1, pp, 2 * pp, 4 * pp})
        per_replica = self.global_batch // max(dp * sh, 1)
        out = []
        for m in cand:
            if m < 1 or per_replica % m:
                continue
            if pp > 1 and m < pp:
                continue
            out.append(m)
        return out

    def candidates(self):
        """Legal (dp, mp, pp, sharding, n_micro, remat) combos: mesh
        factorizations of the slice that the hybrid step's own
        divisibility asserts accept."""
        cfg = self.config
        for dp, mp, pp, sh in _factorizations(self.n_devices, 4):
            if mp > self.max_mp or pp > self.max_pp:
                continue
            if cfg.num_layers % pp or cfg.num_heads % mp \
                    or cfg.vocab_size % mp:
                continue
            if self.global_batch % max(dp * sh, 1):
                continue
            for n_micro in self._micro_choices(dp, pp, sh):
                for remat in self.remat_choices:
                    yield dict(dp=dp, mp=mp, pp=pp, sharding=sh,
                               n_micro=n_micro, remat=remat)

    # ---------------------------------------------- stage 2: HBM pre-prune
    def _state_bytes_per_device(self, c):
        """Closed-form params + Adam moments per device — a LOWER bound
        on peak HBM (activations only add), so exceeding the budget here
        is a certain PTMM001 without paying for a trace."""
        import numpy as np
        import jax.numpy as jnp
        cfg = self.config
        h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
        ffn = cfg.intermediate_size
        block = L * ((4 + 2 * ffn // h) * h * h + 13 * h)
        wte = V * h
        wpe_lnf = cfg.max_position_embeddings * h + 2 * h
        pb = jnp.dtype(self.step_kw.get("param_dtype")
                       or np.float32).itemsize
        mb = jnp.dtype(self.step_kw.get("moment_dtype")
                       or np.float32).itemsize
        per_dev_params = (block / (c["mp"] * c["pp"]) + wte / c["mp"]
                          + wpe_lnf)
        # moments additionally ZeRO-1 shard a free dim over `sharding`
        return per_dev_params * pb \
            + per_dev_params * 2 * mb / max(c["sharding"], 1)

    # ------------------------------------------------- stage 3: pre-rank
    def _closed_form_rank(self, cands):
        """Instant closed-form roofline ordering (same chip table) so
        only the most promising candidates pay for a trace: candidates
        whose closed-form working set (weights + state + activations)
        fits the budget go first, fastest first — the memory-blind
        ordering would burn the whole trace budget on dp-heavy plans
        the real memory pass then rejects."""
        from .cost_model import Cluster, CostEstimator, ModelSpec
        import jax.numpy as jnp
        import numpy as np
        cfg = self.config
        pb = jnp.dtype(self.step_kw.get("param_dtype")
                       or np.float32).itemsize
        mb = jnp.dtype(self.step_kw.get("moment_dtype")
                       or np.float32).itemsize
        spec = ModelSpec(hidden=cfg.hidden_size, layers=cfg.num_layers,
                         seq_len=self.seq_len, vocab_size=cfg.vocab_size,
                         heads=cfg.num_heads,
                         ffn_mult=cfg.intermediate_size // cfg.hidden_size,
                         param_bytes=pb, optimizer_state_per_param=2 * mb)
        est = CostEstimator(spec, Cluster(
            self.n_devices, peak_flops=self.chip["peak_flops"],
            hbm_bandwidth=self.chip["hbm_bw"],
            hbm_bytes=self.chip["hbm_gb"] * 1024 ** 3,
            ici_bandwidth=self.chip["ici_bw"],
            name=self.chip.get("name", "custom")))
        scored = []
        for c in cands:
            st = {"dp": c["dp"], "mp": c["mp"], "pp": c["pp"],
                  "sharding": c["sharding"],
                  "micro_batches": c["n_micro"],
                  "global_batch": self.global_batch,
                  "recompute": bool(c["remat"])}
            cost = est.estimate(st)
            fits = cost.memory_bytes <= self.hbm_budget
            # rank on the full-overlap roofline, the closed form's
            # closest analog of the trace model's max() verdict
            t = cost.breakdown.get("roofline_ms", cost.time_ms)
            scored.append((not fits, t, cost.memory_bytes, c))
        # interleave speed-first and memory-first orderings: the closed
        # form underestimates activation peaks (it has no liveness), so
        # a pure speed ordering burns the trace budget on plans the real
        # memory pass rejects, while a pure memory ordering never traces
        # the fast end — alternating picks covers both frontiers
        by_time = sorted(scored, key=lambda t: (t[0], t[1]))
        by_mem = sorted(scored, key=lambda t: (t[2], t[1]))
        out, seen = [], set()
        for pair in zip(by_time, by_mem):
            for s in pair:
                key = id(s[3])
                if key not in seen:
                    seen.add(key)
                    out.append(s[3])
        return out

    # --------------------------------------------- stage 4: trace + score
    def _trace_plan(self, c):
        """Build the candidate abstractly on a virtual mesh and price it
        with the trace-based cost/memory passes. Returns a Plan (best
        wire dtype chosen by the EQuARX what-if already carried in the
        CostSummary)."""
        import jax
        from ...analysis.passes.cost import estimate_jaxpr_cost
        from ...analysis.passes.memory import estimate_jaxpr_peak
        from ...models.gpt import GPTHybridTrainStep, model_flops_per_token

        schedule = self.pipeline_schedule if c["pp"] > 1 else "gpipe"
        with virtual_hcg(dp=c["dp"], mp=c["mp"], pp=c["pp"],
                         sharding=c["sharding"]) as hcg:
            step = GPTHybridTrainStep.abstract(
                self.config, hcg, n_micro=c["n_micro"], remat=c["remat"],
                pipeline_schedule=schedule, **self.step_kw)
            jaxpr = step.step_jaxpr(self.global_batch, self.seq_len)
            in_divs, donated = step.step_arg_divisors()
            axis_sizes = {k: int(v)
                          for k, v in dict(step.mesh.shape).items()}
        cost = estimate_jaxpr_cost(jaxpr, in_divisors=in_divs,
                                   axis_sizes=axis_sizes, chip=self.chip)
        mem = estimate_jaxpr_peak(jaxpr, in_divisors=in_divs,
                                  donated=donated)
        # the no-donate walk only informs requires_donation/extras —
        # skip it for plans the donated peak already rejects (the walk
        # over a 13B jaxpr is half the per-candidate memory-pass cost)
        mem_nodonate = None
        if mem.peak_bytes <= self.hbm_budget:
            mem_nodonate = estimate_jaxpr_peak(jaxpr, in_divisors=in_divs,
                                               donated=None)
        del jaxpr

        # wire-dtype dimension: the summary already carries the int8
        # what-if for the identical schedule — pick the faster wire
        step_ms = cost.step_ms
        wire = None
        if "int8" in self.wire_dtypes:
            step_ms_i8 = max(cost.compute_ms, cost.hbm_ms,
                             cost.comm_ms_int8, 1e-9)
            if step_ms_i8 < step_ms and cost.comm_bytes_int8 \
                    < cost.comm_bytes:
                step_ms, wire = step_ms_i8, "int8"
        bound = cost.bound_if_int8 if wire == "int8" else cost.bound

        fpt, _ = model_flops_per_token(self.config, self.seq_len)
        tokens = self.global_batch * self.seq_len
        step_s = step_ms / 1e3
        tps_chip = tokens / step_s / self.n_devices
        mfu = tps_chip * fpt / self.chip["peak_flops"]

        plan = Plan(
            dp=c["dp"], mp=c["mp"], pp=c["pp"], sharding=c["sharding"],
            n_micro=c["n_micro"], remat=c["remat"],
            pipeline_schedule=schedule, donate=True, wire_dtype=wire,
            global_batch=self.global_batch, seq_len=self.seq_len,
            chip=self.chip.get("name", self.chip_name),
            n_devices=self.n_devices, step_ms=step_ms,
            predicted_mfu=mfu, peak_hbm_bytes=mem.peak_bytes,
            bound=bound, compute_ms=cost.compute_ms, hbm_ms=cost.hbm_ms,
            comm_ms=cost.comm_ms_int8 if wire == "int8"
            else cost.comm_ms,
            tokens_per_sec_per_chip=tps_chip,
            requires_donation=(mem_nodonate is not None
                               and mem_nodonate.peak_bytes
                               > self.hbm_budget),
            traced=True,
            extras={"comm_ms_f32": round(cost.comm_ms, 4),
                    "int8_wire_reduction": round(
                        cost.int8_wire_reduction, 3),
                    **({"peak_hbm_gb_no_donate": round(
                        mem_nodonate.peak_bytes / 1024 ** 3, 3)}
                       if mem_nodonate is not None else {})})
        if mem.peak_bytes > self.hbm_budget:
            plan.feasible = False
            plan.reject_reason = (
                f"PTMM001: predicted peak HBM "
                f"{mem.peak_bytes / 1024 ** 3:.2f} GiB exceeds the "
                f"{self.hbm_budget / 1024 ** 3:.2f} GiB "
                f"{plan.chip} budget")
        return plan

    # ------------------------------------------------------------ search
    def search(self, top_k=None) -> PlanReport:
        t0 = time.perf_counter()
        report = PlanReport(model=self.model_name,
                            chip=self.chip.get("name", self.chip_name),
                            n_devices=self.n_devices)
        survivors = []
        for c in self.candidates():
            report.n_candidates += 1
            state = self._state_bytes_per_device(c)
            if state > self.hbm_budget:
                report.pruned.append(Plan(
                    dp=c["dp"], mp=c["mp"], pp=c["pp"],
                    sharding=c["sharding"], n_micro=c["n_micro"],
                    remat=c["remat"], global_batch=self.global_batch,
                    seq_len=self.seq_len, n_devices=self.n_devices,
                    chip=self.chip.get("name", self.chip_name),
                    peak_hbm_bytes=state, feasible=False,
                    reject_reason=(
                        f"params+optimizer state alone "
                        f"{state / 1024 ** 3:.1f} GiB/device exceeds "
                        f"the {self.hbm_budget / 1024 ** 3:.1f} GiB "
                        f"budget")))
                continue
            survivors.append(c)
        oom_families = set()
        queue = list(self._closed_form_rank(survivors))
        while queue:
            # trace budget: max_traces finalists, but keep going (up to
            # 3x) while nothing feasible has landed yet — an empty
            # answer on a plannable model is worse than a slow plan
            if report.n_traced >= self.max_traces and report.plans:
                break
            if report.n_traced >= 3 * self.max_traces:
                break
            c = queue.pop(0)
            family = (c["dp"], c["mp"], c["pp"], c["sharding"],
                      c["remat"])
            if family in oom_families:
                continue
            plan = self._trace_plan(c)
            report.n_traced += 1
            if plan.feasible:
                report.plans.append(plan)
                continue
            report.pruned.append(plan)
            # n_micro barely moves the peak (1f1b keeps O(pp) micros
            # live; the pp=1 grad-accum scan stacks every micro's
            # residuals) — don't re-trace the same OOM (mesh, remat)
            # family for other micro-batch counts
            oom_families.add(family)
            if not c["remat"]:
                # this mesh was promising enough to trace but OOMs
                # without remat: its remat siblings trade ~1/3 more
                # compute for the activation memory that sank it —
                # promote them to the front of the queue
                mesh_key = family[:4]
                promoted = [q for q in queue
                            if (q["dp"], q["mp"], q["pp"],
                                q["sharding"]) == mesh_key
                            and q["remat"]]
                rest = [q for q in queue if q not in promoted]
                queue = promoted + rest
        # roofline max() can tie meshes on step time (same compute,
        # comm hidden under it) — break toward fewer wire bytes, then
        # lower peak HBM: the plan with slack, not the knife-edge one
        report.plans.sort(key=lambda p: (p.step_ms, p.comm_ms,
                                         p.peak_hbm_bytes))
        if top_k is not None:
            report.plans = report.plans[:top_k]
        report.planner_s = time.perf_counter() - t0
        return report


def price_config(config, mesh_degrees, n_micro=1, remat=True,
                 pipeline_schedule="1f1b", global_batch=8, seq_len=1024,
                 chip="v5e", step_kw=None, wire_dtypes=(None,)) -> Plan:
    """Price ONE fully-specified configuration with the planner's
    trace-based scorer — the anchor path ``bench.py`` /
    ``tests/test_planner.py`` use to pit the planner's winner against
    the hand-written 13B config on identical terms."""
    d = dict(dp=1, mp=1, pp=1, sharding=1)
    d.update(mesh_degrees)
    n = d["dp"] * d["mp"] * d["pp"] * d["sharding"]
    p = Planner(config, n, chip=chip, global_batch=global_batch,
                seq_len=seq_len, step_kw=step_kw,
                pipeline_schedule=pipeline_schedule,
                wire_dtypes=wire_dtypes)
    return p._trace_plan(dict(d, n_micro=n_micro, remat=remat))


def plan_gpt(model="gpt_13b", devices=16, chip="v5e", global_batch=None,
             seq_len=None, top_k=5, max_traces=8, **kw) -> PlanReport:
    """Plan a named GPT config (``gpt_tiny/345m/1p3b/13b``) or a
    ``GPTConfig`` instance on ``devices`` chips of ``chip``. Defaults
    (batch/seq/dtypes) mirror the bench configs so the winner is
    directly comparable to the hand-written ``*_predicted`` rows."""
    registry = _model_registry()
    if isinstance(model, str):
        if model not in registry:
            raise KeyError(
                f"unknown model {model!r}; choose from "
                f"{sorted(registry)} or pass a GPTConfig")
        cfg_fn, batch0, seq0, step_kw = registry[model]
        config, name = cfg_fn(), model
    else:
        config, name = model, getattr(model, "name", "custom")
        batch0, seq0, step_kw = 8, config.max_position_embeddings, {}
    planner = Planner(config, devices, chip=chip,
                      global_batch=global_batch or batch0,
                      seq_len=seq_len or seq0,
                      step_kw=kw.pop("step_kw", step_kw),
                      max_traces=max_traces, model_name=name, **kw)
    return planner.search(top_k=top_k)


# ---------------------------------------------------------------------------
# serving-side search: (decode bucket, page size, quantize)
# ---------------------------------------------------------------------------

def plan_serving(config="345m", chip="v5e",
                 concurrency_choices=(4, 8, 16, 32),
                 page_sizes=(32, 64, 128), quantize_choices=(None, "int8"),
                 headroom=0.9, top_k=5) -> dict:
    """The same search shape over the serving engine's plan space:
    decode-batch bucket (concurrency), KV page size, and ``quantize=``,
    each candidate priced by ``serving/predict.py``'s trace-based row
    (the REAL decode program's jaxpr through the cost pass). Feasibility
    is weights + KV pool against the chip HBM budget; ranking is
    predicted decode tokens/s. Returns ``{"plans": [...], "best": ...,
    "planner_s": ...}`` rows ready for ``ServingEngine(engine_bucket=,
    page_size=, quantize=)``."""
    from ...observability.instrument import chip_specs
    from ...serving.predict import predicted_serving_row
    t0 = time.perf_counter()
    spec = chip_specs(chip)
    budget_mb = spec["hbm_gb"] * 1024 * headroom
    plans, pruned = [], []
    for quantize in quantize_choices:
        for ps in page_sizes:
            for conc in concurrency_choices:
                row = predicted_serving_row(config, conc, ps, chip,
                                            quantize=quantize)
                need_mb = row["weights_mb"] + row["kv_pool_mb"]
                row["hbm_mb"] = round(need_mb, 1)
                row["feasible"] = need_mb <= budget_mb
                if row["feasible"]:
                    plans.append(row)
                else:
                    row["reject_reason"] = (
                        f"weights+pool {need_mb / 1024:.1f} GiB exceed "
                        f"the {budget_mb / 1024:.1f} GiB budget")
                    pruned.append(row)
    plans.sort(key=lambda r: -r["predicted_tokens_per_sec"])
    return {
        "config": config, "chip": spec.get("name", chip),
        "plans": plans[:top_k], "n_pruned": len(pruned),
        "pruned": pruned, "best": plans[0] if plans else None,
        "planner_s": round(time.perf_counter() - t0, 3),
    }
