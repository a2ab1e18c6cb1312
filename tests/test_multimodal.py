"""Multimodal DAG pipeline: shape bucketing, bitwise parity, end-to-end.

The two bucketing guarantees of the subsystem (ISSUE satellites):

* **bounded recompiles** — the jit compile-cache size of every
  variable-length stage op stays <= the bucket count under randomized
  variable-length vision batches;
* **bitwise parity** — bucketed and unbucketed execution produce
  identical loss and gradient bits on a tiny model (the padding is
  arithmetically invisible, not just approximately so).

Plus: the real jitted DAG run on the actor runtime matches the
fixed-order reference executor bitwise under deterministic reduction, BFW
split backward matches the fused backward bitwise, and the registered
multimodal archs are reachable from the train CLI.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax

from repro.core import HintKind
from repro.core.taskgraph import Kind, Task
from repro.data.lengths import bucket_for, length_skew, sample_token_lengths
from repro.data.synthetic import multimodal_batch
from repro.multimodal import MultimodalStageFns, multimodal_model
from repro.pipeline.stagefn import ActorStageProgram, StageFnOptions
from repro.runtime.rrfp import ActorConfig, ActorDriver, ChaosConfig

M, ROWS, SEQ = 5, 2, 16
BUCKETS = (8, 16, 24)


@pytest.fixture(scope="module")
def tiny():
    model = multimodal_model(
        "qwen2-vl-2b", enc_stages=2, lm_stages=2, enc_layers_per_stage=1,
        lm_layers_per_stage=1, text_seq=SEQ, fusion_slots=4,
        mean_enc_tokens=14, buckets=BUCKETS)
    params = model.init_stage_params(jax.random.key(0))
    fns = MultimodalStageFns(model, StageFnOptions(
        mb_rows=ROWS, seq_len=SEQ, loss_scale=1.0 / (M * ROWS * SEQ)))
    return model, params, fns


def run_step(model, params, fns, *, bucketing=True, split=False, cap=0,
             chaos=None, seed=0, step=0, mode="hint", deterministic=True):
    cfg = model.cfg
    batch = multimodal_batch(cfg, M, ROWS, seed=0, step=step,
                             bucketing=bucketing)
    programs = [
        ActorStageProgram(fns, s, params[s], {}, batch,
                          split_backward=split,
                          deterministic_reduction=deterministic)
        for s in range(cfg.num_stages)
    ]
    spec = cfg.spec(M, split_backward=split)
    acfg = ActorConfig(
        mode=mode, hint=HintKind.BFW if split else HintKind.BF,
        fixed_order="zb" if split else "1f1b", w_defer_cap=cap,
        deadlock_timeout=120.0, chaos=chaos, seed=seed)
    ActorDriver(spec, None, acfg).run_threaded(list(programs))
    for p in programs:
        p.finalize()
    return programs


def loss_grad_bits(programs):
    loss = np.asarray(sum(p.loss_acc for p in programs)).tobytes()
    grads = b"".join(np.asarray(g).tobytes()
                     for p in programs for g in jax.tree.leaves(p.d_stage))
    return loss, grads


# ---------------------------------------------------------------------------
# the shared length sampler
# ---------------------------------------------------------------------------
class TestLengthSampler:
    def test_mean_one_skew(self):
        rng = np.random.default_rng(0)
        s = length_skew(20000, 0.6, rng)
        assert abs(s.mean() - 1.0) < 0.05

    def test_deterministic_in_seed_step(self):
        a = sample_token_lengths(8, 24, seed=3, step=5)
        b = sample_token_lengths(8, 24, seed=3, step=5)
        c = sample_token_lengths(8, 24, seed=3, step=6)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)

    def test_bounds_and_buckets(self):
        lens = sample_token_lengths(64, 24, seed=0, lo=4, hi=24)
        assert lens.min() >= 4 and lens.max() <= 24
        assert bucket_for(5, BUCKETS) == 8
        assert bucket_for(8, BUCKETS) == 8
        assert bucket_for(9, BUCKETS) == 16
        assert bucket_for(99, BUCKETS) == 24  # clamps to the largest

    def test_workloads_share_the_sampler(self):
        """The DES workload skew is the same draw as the shared sampler."""
        from benchmarks.workloads import stage_costs

        cm = stage_costs("qwen3-1.7b", "vit-h", pp=8, seed=4)
        rng = np.random.default_rng(4)
        expect = length_skew(64, 0.6, rng)
        assert np.array_equal(cm.mb_skew[0], expect)


# ---------------------------------------------------------------------------
# bucketing: compile-cache bound
# ---------------------------------------------------------------------------
class TestShapeBucketing:
    def test_batch_shapes_are_bucketed(self, tiny):
        model, _, _ = tiny
        batch = multimodal_batch(model.cfg, 16, ROWS, seed=1, step=0)
        pads = {e.shape[1] for e in batch["enc_embeds"]}
        assert pads <= set(BUCKETS)
        for e, n in zip(batch["enc_embeds"], batch["enc_lens"]):
            assert e.shape[1] >= n
            assert not e[:, n:].any()  # exact-zero padding

    def test_compile_cache_bounded_by_bucket_count(self, tiny):
        """Randomized variable lengths over many steps: the jit cache of
        every variable-shape op stays <= len(buckets)."""
        model, params, fns = tiny
        cfg = model.cfg
        seen = set()
        for step in range(6):  # enough steps to visit every bucket
            batch = multimodal_batch(cfg, M, ROWS, seed=11, step=step)
            seen |= {e.shape[1] for e in batch["enc_embeds"]}
            programs = [
                ActorStageProgram(fns, s, params[s], {}, batch)
                for s in range(cfg.num_stages)
            ]
            acfg = ActorConfig(mode="hint", hint=HintKind.BF,
                               deadlock_timeout=120.0)
            ActorDriver(cfg.spec(M), None, acfg).run_threaded(list(programs))
        assert len(seen) > 1, "scenario must exercise multiple buckets"
        for (op, stage), size in fns.compile_cache_sizes().items():
            assert size <= len(BUCKETS), (
                f"{op} at stage {stage}: {size} traces > "
                f"{len(BUCKETS)} buckets")

    def test_unbucketed_retraces_per_distinct_length(self, tiny):
        """Control: without bucketing the cache grows with distinct
        lengths (what bucketing is bounding)."""
        model, params, _ = tiny
        cfg = model.cfg
        fns = MultimodalStageFns(model, StageFnOptions(
            mb_rows=ROWS, seq_len=SEQ, loss_scale=1.0 / (M * ROWS * SEQ)))
        lengths = set()
        for step in range(4):
            batch = multimodal_batch(cfg, M, ROWS, seed=11, step=step,
                                     bucketing=False)
            lengths |= {e.shape[1] for e in batch["enc_embeds"]}
            programs = [
                ActorStageProgram(fns, s, params[s], {}, batch)
                for s in range(cfg.num_stages)
            ]
            acfg = ActorConfig(mode="hint", hint=HintKind.BF,
                               deadlock_timeout=120.0)
            ActorDriver(cfg.spec(M), None, acfg).run_threaded(list(programs))
        sizes = fns.compile_cache_sizes()
        assert sizes[("forward", 0)] == len(lengths)


# ---------------------------------------------------------------------------
# bitwise parity
# ---------------------------------------------------------------------------
class TestBitwiseParity:
    def test_bucketed_equals_unbucketed(self, tiny):
        """Loss AND gradient bits identical with and without bucketing."""
        model, params, fns = tiny
        a = loss_grad_bits(run_step(model, params, fns, bucketing=True))
        b = loss_grad_bits(run_step(model, params, fns, bucketing=False))
        assert a[0] == b[0], "loss bits diverged under bucketing"
        assert a[1] == b[1], "gradient bits diverged under bucketing"

    def test_bucketed_equals_unbucketed_across_steps(self, tiny):
        model, params, fns = tiny
        for step in (1, 2):
            a = loss_grad_bits(run_step(model, params, fns, step=step))
            b = loss_grad_bits(run_step(model, params, fns, step=step,
                                        bucketing=False))
            assert a == b, f"parity broke at step {step}"

    def test_chaotic_run_matches_fixed_order_reference(self, tiny):
        """Deterministic reduction: a chaotic DAG actor run reproduces the
        precommitted fixed-order execution bit for bit."""
        model, params, fns = tiny
        chaos = ChaosConfig(seed=5, latency_base=1e-3, reorder_prob=0.5,
                            reorder_window=5e-3, duplicate_prob=0.3,
                            straggler=((1, 2.0),), stall_prob=0.1,
                            stall_scale=3e-3)
        a = loss_grad_bits(run_step(model, params, fns))
        b = loss_grad_bits(run_step(model, params, fns, chaos=chaos, seed=9))
        c = loss_grad_bits(run_step(model, params, fns, mode="precommitted"))
        assert a == b, "chaotic run diverged from clean run"
        assert a == c, "hint run diverged from fixed-order reference"

    def test_bfw_split_matches_fused_bitwise(self, tiny):
        """B(dX) + W(dW) == fused backward, bitwise, on the DAG — and the
        BFW hint run == the pre-committed ZB fixed-order reference."""
        model, params, fns = tiny
        a = loss_grad_bits(run_step(model, params, fns))
        d = loss_grad_bits(run_step(model, params, fns, split=True, cap=2))
        e = loss_grad_bits(run_step(model, params, fns, split=True,
                                    mode="precommitted"))
        assert a == d
        assert d == e

    def test_w_defer_cap_bounds_stash(self, tiny):
        model, params, fns = tiny
        progs = run_step(model, params, fns, split=True, cap=2)
        assert max(p.w_high_water for p in progs) <= 2
        assert all(p.w_outstanding() == 0 for p in progs)


# ---------------------------------------------------------------------------
# end-to-end: training decreases loss; both archs + CLI reachability
# ---------------------------------------------------------------------------
class TestEndToEnd:
    @pytest.mark.slow
    def test_loss_decreases_qwen(self):
        from repro.launch.train import train_multimodal

        class A:  # minimal args namespace
            arch = "qwen2-vl-2b"
            runtime = "actor"
            substrate = "thread"
            schedule = "rrfp"
            hint = "bfw"
            split_backward = True
            w_defer_cap = 2
            stages = 4
            microbatches = 4
            mb_rows = 1
            seq = 16
            steps = 6
            layers = None
            lr = 5e-3
            seed = 0
            chaos = None
            record_trace = None
            replay_trace = None
            deadlock_timeout = 300.0
            full_size = False

        losses = train_multimodal(A())
        assert losses[-1] < losses[0]

    def test_seamless_runs_one_step(self):
        model = multimodal_model(
            "seamless-m4t-large-v2", enc_stages=1, lm_stages=1,
            enc_layers_per_stage=1, lm_layers_per_stage=1, text_seq=8,
            fusion_slots=2, mean_enc_tokens=10, buckets=(8, 16))
        params = model.init_stage_params(jax.random.key(1))
        fns = MultimodalStageFns(model, StageFnOptions(
            mb_rows=1, seq_len=8, loss_scale=1.0 / 16))
        batch = multimodal_batch(model.cfg, 2, 1, seed=0, step=0)
        programs = [ActorStageProgram(fns, s, params[s], {}, batch)
                    for s in range(model.cfg.num_stages)]
        acfg = ActorConfig(mode="hint", hint=HintKind.BF,
                           deadlock_timeout=120.0)
        res = ActorDriver(model.cfg.spec(2), None, acfg).run_threaded(
            list(programs))
        assert len(res.end) == model.cfg.spec(2).total_tasks()
        assert np.isfinite(float(sum(p.loss_acc for p in programs)))

    def test_archs_rejected_and_accepted(self):
        from repro.multimodal import multimodal_config

        with pytest.raises(ValueError, match="not a multimodal arch"):
            multimodal_config("deepseek-7b")
        for arch in ("qwen2-vl-2b", "seamless-m4t-large-v2"):
            cfg = multimodal_config(arch)
            assert cfg.num_stages == cfg.enc_stages + 1 + cfg.lm_stages

    def test_fusion_fan_in_payload_routing(self, tiny):
        """The fusion stage's F sees one payload per incoming edge."""
        model, params, fns = tiny
        cfg = model.cfg
        batch = multimodal_batch(cfg, M, ROWS, seed=0, step=0)
        prog = ActorStageProgram(
            fns, cfg.fusion_stage, params[cfg.fusion_stage], {}, batch)
        h_enc = jax.numpy.zeros((ROWS, BUCKETS[0], cfg.d_enc))
        h_txt = jax.numpy.zeros((ROWS, cfg.text_seq, cfg.d_model))
        y = prog(Task(Kind.F, cfg.fusion_stage, 0),
                 {cfg.enc_stages - 1: h_enc, cfg.text_stage: h_txt})
        assert y.shape == (ROWS, cfg.fused_seq, cfg.d_model)
        dx = prog(Task(Kind.B, cfg.fusion_stage, 0),
                  jax.numpy.zeros_like(y))
        assert set(dx) == {cfg.enc_stages - 1, cfg.text_stage}
        assert dx[cfg.enc_stages - 1].shape == h_enc.shape
        assert dx[cfg.text_stage].shape == h_txt.shape


# ---------------------------------------------------------------------------
# the chain's stage program on the DAG
# ---------------------------------------------------------------------------
class TestStageProgram:
    def test_stage_modules_carry_stage_and_op(self, tiny):
        """Every DAG stage's callables lower as ``jit_stage{s}_F``/``_B``,
        and ``_dX``/``_W`` for split backward (no dX at a source stage)."""
        import jax.numpy as jnp

        from repro.pipeline.stagefn import zero_accumulators

        model, params, fns = tiny
        cfg = model.cfg
        batch = multimodal_batch(cfg, M, ROWS, seed=0, step=0)
        graph = cfg.stage_graph()
        ys = {}
        for s in graph.topological_order():
            preds = graph.preds(s)
            x = ({p: ys[p] for p in preds} if len(preds) > 1
                 else ys[preds[0]] if preds else None)
            bm = fns.microbatch(batch, 0, s)
            ys[s], _ = fns.forward(s)(params[s], {}, x, bm)
            args = (params[s], {}, x, jnp.zeros_like(ys[s]), bm)
            acc, _ = zero_accumulators(params[s], {})
            lowered = {
                "F": [fns.forward(s).lower(params[s], {}, x, bm)],
                "B": [fns.backward(s).lower(*args),
                      fns.backward_into(s).lower(acc, *args)],
                "W": [fns.weight_grad(s).lower(*args),
                      fns.weight_grad_into(s).lower(acc, *args)],
            }
            if x is not None:
                lowered["dX"] = [fns.backward_dx(s).lower(*args)]
            assert ("dX" in lowered) == (s not in graph.sources())
            for op, lows in lowered.items():
                for low in lows:
                    assert f"module @jit_stage{s}_{op} " in low.as_text()

    def test_eager_step_matches_deterministic_in_bf16(self, tiny):
        """A bf16 DAG step in eager mode folds M microbatches inside each
        stage's B callable, and its loss and grads match the deterministic
        microbatch-order fold within bf16 rounding."""
        import dataclasses

        import jax.numpy as jnp

        from repro.multimodal import MultimodalModel

        cfg = tiny[0].cfg
        model = MultimodalModel(dataclasses.replace(
            cfg, lm_cfg=dataclasses.replace(cfg.lm_cfg, dtype=jnp.bfloat16)))
        params = model.init_stage_params(jax.random.key(0))
        fns = MultimodalStageFns(model, StageFnOptions(
            mb_rows=ROWS, seq_len=SEQ, loss_scale=1.0 / (M * ROWS * SEQ)))
        eager = run_step(model, params, fns, deterministic=False)
        det = run_step(model, params, fns, deterministic=True)
        for pe, pd in zip(eager, det):
            assert pe.folded_in_callable == M
            assert pd.folded_in_callable == 0
            assert abs(pe.loss_sum - pd.loss_sum) <= 1e-6 * abs(pd.loss_sum)
            for ge, gd in zip(jax.tree.leaves(pe.d_stage),
                              jax.tree.leaves(pd.d_stage)):
                assert ge.dtype == gd.dtype == jnp.bfloat16
                gd32 = gd.astype(jnp.float32)
                scale = float(jnp.max(jnp.abs(gd32))) + 1e-30
                err = float(jnp.max(jnp.abs(ge.astype(jnp.float32) - gd32)))
                assert err <= 2 ** -6 * scale
