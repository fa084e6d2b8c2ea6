"""MoE + expert parallelism: dense reference vs EP psum vs EP all-to-all.

EP strategies run under shard_map on the virtual 8-device CPU mesh; the same
programs compile for a real ICI ep axis."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models import moe
from dynamo_tpu.models.moe import MoeConfig
from dynamo_tpu.parallel import mesh as meshlib


def _shard_experts(params, spec_axis):
    """Shard the expert-stacked layer weights on their leading dim."""
    def is_expert(name):
        return name in ("w_gate", "w_up", "w_down")
    return params, is_expert


class TestRouting:
    def test_topk_weights_normalized(self):
        cfg = MoeConfig.tiny_moe()
        p = moe.init_layer_params(jax.random.PRNGKey(0), cfg)
        x = jnp.asarray(np.random.default_rng(0).standard_normal((10, cfg.hidden_size)), jnp.float32)
        w, i = moe.route(p, cfg, x)
        assert w.shape == (10, cfg.num_experts_per_tok)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        assert int(i.max()) < cfg.num_experts

    def test_expert_load_counts(self):
        cfg = MoeConfig.tiny_moe()
        topi = jnp.asarray([[0, 1], [1, 2], [1, 3]])
        load = moe.expert_load(cfg, topi)
        assert load.tolist() == [1, 3, 1, 1]


class TestEpEquivalence:
    def setup_method(self):
        self.cfg = MoeConfig.tiny_moe(num_experts=8, moe_intermediate_size=32)
        self.p = moe.init_layer_params(jax.random.PRNGKey(1), self.cfg)
        rng = np.random.default_rng(2)
        self.x = jnp.asarray(rng.standard_normal((16, self.cfg.hidden_size)), jnp.float32)
        self.ref = moe.moe_ffn(self.p, self.cfg, self.x)

    def test_gather_matches_dense(self):
        """The sparse serving path (token-sorted grouped multiplication,
        T*K FLOPs) is exact: identical to the dense all-expert reference."""
        got = moe.moe_ffn_grouped(self.p, self.cfg, self.x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self.ref), rtol=2e-5, atol=2e-5
        )

    @pytest.mark.parametrize("ep", [2, 4])
    def test_psum_matches_dense(self, ep):
        mesh = meshlib.make_mesh(tp=ep, devices=jax.devices()[:ep])
        expert_spec = {
            "w_gate": P(meshlib.AXIS_TP), "w_up": P(meshlib.AXIS_TP),
            "w_down": P(meshlib.AXIS_TP),
        }
        in_specs = (
            {k: expert_spec.get(k, P()) for k in self.p}, P(),
        )
        fn = meshlib.shard_map(
            lambda p, x: moe.moe_ffn_ep_psum(p, self.cfg, x, meshlib.AXIS_TP),
            mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False,
        )
        got = fn(self.p, self.x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(self.ref), atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("ep", [2, 4])
    def test_a2a_matches_dense(self, ep):
        # generous capacity so no token drops -> exact equality with dense
        cfg = MoeConfig.tiny_moe(
            num_experts=8, moe_intermediate_size=32, capacity_factor=8.0
        )
        mesh = meshlib.make_mesh(tp=ep, devices=jax.devices()[:ep])
        expert_spec = {
            "w_gate": P(meshlib.AXIS_TP), "w_up": P(meshlib.AXIS_TP),
            "w_down": P(meshlib.AXIS_TP),
        }
        in_specs = (
            {k: expert_spec.get(k, P()) for k in self.p},
            P(meshlib.AXIS_TP),          # tokens sharded
        )
        fn = meshlib.shard_map(
            lambda p, x: moe.moe_ffn_ep_a2a(p, cfg, x, meshlib.AXIS_TP),
            mesh=mesh, in_specs=in_specs, out_specs=P(meshlib.AXIS_TP),
            check_vma=False,
        )
        got = fn(self.p, self.x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(self.ref), atol=1e-5, rtol=1e-5)

    def test_a2a_capacity_drops_bounded(self):
        """With tight capacity the output differs only for dropped slots —
        shape and finiteness hold (Switch-style graceful degradation)."""
        cfg = MoeConfig.tiny_moe(
            num_experts=8, moe_intermediate_size=32, capacity_factor=0.5
        )
        mesh = meshlib.make_mesh(tp=2, devices=jax.devices()[:2])
        expert_spec = {
            "w_gate": P(meshlib.AXIS_TP), "w_up": P(meshlib.AXIS_TP),
            "w_down": P(meshlib.AXIS_TP),
        }
        in_specs = ({k: expert_spec.get(k, P()) for k in self.p}, P(meshlib.AXIS_TP))
        fn = meshlib.shard_map(
            lambda p, x: moe.moe_ffn_ep_a2a(p, cfg, x, meshlib.AXIS_TP),
            mesh=mesh, in_specs=in_specs, out_specs=P(meshlib.AXIS_TP), check_vma=False,
        )
        got = np.asarray(fn(self.p, self.x))
        assert got.shape == self.ref.shape
        assert np.isfinite(got).all()


class TestMoeModel:
    def test_forward_and_logits(self):
        cfg = MoeConfig.tiny_moe()
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        S = 8
        tokens = jnp.arange(S)[None]
        positions = jnp.arange(S)[None]

        from dynamo_tpu.ops import attention as att

        def attend(q, k, v, li):
            return att.causal_attention(q[0], k[0], v[0])[None]

        hidden = moe.forward(params, cfg, tokens, positions, attend)
        assert hidden.shape == (1, S, cfg.hidden_size)
        logits = moe.lm_logits(params, cfg, hidden[0])
        assert logits.shape == (S, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_forward_deterministic(self):
        cfg = MoeConfig.tiny_moe()
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        from dynamo_tpu.ops import attention as att

        def attend(q, k, v, li):
            return att.causal_attention(q[0], k[0], v[0])[None]

        tokens = jnp.arange(6)[None]
        pos = jnp.arange(6)[None]
        h1 = moe.forward(params, cfg, tokens, pos, attend)
        h2 = moe.forward(params, cfg, tokens, pos, attend)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


class TestMoeEngine:
    """TpuEngine serving an MoE model end-to-end (experts sharded over the
    tp axis via GSPMD; registry-driven model dispatch)."""

    def _engine(self, tp=1):
        from dynamo_tpu.engine.engine import TpuEngine, TpuEngineConfig
        from dynamo_tpu.parallel.mesh import make_mesh

        cfg = TpuEngineConfig(
            model=MoeConfig.tiny_moe(),
            num_blocks=64, block_size=4, max_batch_size=4, max_context=128,
            prefill_buckets=(16, 32, 64, 128), tp=tp,
        )
        return TpuEngine(cfg, mesh=make_mesh(tp=tp, devices=jax.devices()[:tp]))

    async def _run(self, engine, rid, prompt, n=8):
        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest, SamplingOptions, StopConditions,
        )
        from dynamo_tpu.runtime import Context

        req = PreprocessedRequest(
            request_id=rid, model="m", token_ids=prompt,
            stop=StopConditions(max_tokens=n, ignore_eos=True),
            sampling=SamplingOptions(temperature=0.0),
        )
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
        return toks

    async def test_moe_engine_generates(self):
        e = self._engine()
        try:
            t1 = await self._run(e, "a", list(range(40, 60)))
            t2 = await self._run(e, "b", list(range(40, 60)))
            assert len(t1) == 8
            assert t1 == t2
        finally:
            e.stop()

    async def test_moe_tp2_equivalence(self):
        e1 = self._engine(tp=1)
        try:
            ref = await self._run(e1, "a", list(range(10, 30)))
        finally:
            e1.stop()
        e2 = self._engine(tp=2)
        try:
            got = await self._run(e2, "b", list(range(10, 30)))
        finally:
            e2.stop()
        assert got == ref
