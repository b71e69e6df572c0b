"""Mesh-sharded serving data plane: whole-engine token parity + units.

Threading a ``jax.sharding.Mesh`` through the engine changes *where*
tensors live — weights and LoRA slots over "model", KV pages and
batch-state vectors over "data" — but must never change *which* tokens
are produced (DESIGN §4: exact-reductions mode keeps every FP
reduction in single-device order). This suite A/Bs ``mesh_shape=None``
against (1,1)/(2,1)/(1,2)/(2,2) across paged/dense, fused on/off,
greedy/sampled, and the prefix cache with shared-page refcounts; plus
unit tests for ``make_serving_mesh`` validation, ``fit_spec``
warn-once, per-shard telemetry, ``EngineCluster`` device budgeting and
``build_system(mesh_shape=...)``.

Mesh cases needing N devices skip unless the host exposes them — CI's
sharded-smoke job runs with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Request, SamplingParams
from repro.launch.mesh import make_serving_mesh
from repro.models import api
from repro.serving.engine import ChameleonEngine, EngineConfig


def needs(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs {n} devices (set "
               f"XLA_FLAGS=--xla_force_host_platform_device_count=4)")


MESHES = [pytest.param((1, 1), marks=needs(1)),
          pytest.param((2, 1), marks=needs(2)),
          pytest.param((1, 2), marks=needs(2)),
          pytest.param((2, 2), marks=needs(4))]


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("chameleon-llama-7b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


BASE = dict(max_slots=4, max_len=128, n_lora_slots=4, n_adapters=8,
            seed=0)


def make_engine(small_model, mesh_shape, **kw):
    cfg, params = small_model
    return ChameleonEngine(cfg, params, EngineConfig(
        **{**BASE, **kw, "mesh_shape": mesh_shape}))


def run_trace(eng, n=8, seed=0, sample_every=3, max_steps=20_000):
    """Mixed greedy/sampled trace; outputs keyed by *submission order*
    (req_ids are globally monotonic across engine instances, so they
    differ between the A and B arm of a parity test)."""
    rng = np.random.default_rng(seed)
    handles = []
    for i in range(n):
        r = Request(input_len=int(rng.integers(8, 40)),
                    output_len=int(rng.integers(4, 12)),
                    adapter_id=int(rng.integers(0, 8)))
        sp = (SamplingParams(temperature=0.8, top_k=8, seed=i)
              if sample_every and i % sample_every == 2 else None)
        handles.append(eng.submit(r, sampling=sp))
    steps = 0
    while eng.busy() and steps < max_steps:
        eng.step()
        eng.pool.check_invariants(
            free_page_ids=getattr(eng, "free_pages", None))
        steps += 1
    assert not eng.busy(), "engine failed to drain"
    return [h.tokens for h in handles]


def shared_prefix_prompts(n=8, prefix_len=40, n_prefixes=2, seed=11,
                          vocab=256):
    rng = np.random.default_rng(seed)
    pres = [rng.integers(3, vocab, size=prefix_len).tolist()
            for _ in range(n_prefixes)]
    return [pres[i % n_prefixes]
            + rng.integers(3, vocab, size=int(rng.integers(4, 13))).tolist()
            for i in range(n)]


def run_prompts(eng, prompts, adapters, out_len=8, max_steps=20_000):
    handles = [eng.submit(Request(input_len=len(p), output_len=out_len,
                                  adapter_id=a, prompt=list(p)))
               for p, a in zip(prompts, adapters)]
    steps = 0
    while eng.busy() and steps < max_steps:
        eng.step()
        eng.pool.check_invariants(
            free_page_ids=getattr(eng, "free_pages", None))
        steps += 1
    assert not eng.busy(), "engine failed to drain"
    return [h.tokens for h in handles]


# --------------------------------------------------------- token parity
class TestShardedParity:
    @pytest.mark.parametrize("mesh_shape", MESHES)
    @pytest.mark.parametrize("paged", (False, True))
    def test_token_parity_fused(self, small_model, paged, mesh_shape):
        """mesh == no-mesh, token for token, both KV layouts, fused
        hot loop, mixed greedy/sampled traffic."""
        base = run_trace(make_engine(small_model, None, paged=paged,
                                     fused_hotloop=True))
        got = run_trace(make_engine(small_model, mesh_shape, paged=paged,
                                    fused_hotloop=True))
        assert got == base, "mesh sharding changed decoded tokens"

    @pytest.mark.parametrize("mesh_shape", MESHES)
    def test_token_parity_unfused(self, small_model, mesh_shape):
        """The seed two-dispatch loop (decode jit + host sample) must
        hold parity too — it exercises the non-fused logits path."""
        base = run_trace(make_engine(small_model, None, paged=True,
                                     fused_hotloop=False))
        got = run_trace(make_engine(small_model, mesh_shape, paged=True,
                                    fused_hotloop=False))
        assert got == base

    @pytest.mark.parametrize("mesh_shape", MESHES)
    def test_prefix_cache_parity_and_refcounts(self, small_model,
                                               mesh_shape):
        """Prefix cache on a sharded pool: parity vs the no-mesh
        prefix-on engine, pages actually shared (refcounts observed),
        and every refcount back to the tree's own after drain."""
        prompts = shared_prefix_prompts(n=8)
        adapters = [i % 2 for i in range(8)]
        base_eng = make_engine(small_model, None, paged=True,
                               fused_hotloop=True, prefix_cache=True)
        base = run_prompts(base_eng, prompts, adapters)
        eng = make_engine(small_model, mesh_shape, paged=True,
                          fused_hotloop=True, prefix_cache=True)
        got = run_prompts(eng, prompts, adapters)
        assert got == base, "sharded prefix cache changed tokens"
        assert eng.prefix_hit_tokens > 0, "no pages were reused"
        shared = eng.pool.shared_page_ids()
        assert shared, "prefix tree retained no pages"
        assert all(eng.pool.shared_refcount(p) == 1 for p in shared)
        eng.pool.check_invariants(free_page_ids=eng.free_pages)


# ------------------------------------------------------------ telemetry
class TestShardTelemetry:
    def test_no_mesh_no_shard_stats(self, small_model):
        eng = make_engine(small_model, None)
        assert eng.shard_stats() == {}
        assert "mesh_shape" not in eng.stats()

    @pytest.mark.parametrize("mesh_shape", MESHES[1:])
    def test_shard_stats_surface(self, small_model, mesh_shape):
        eng = make_engine(small_model, mesh_shape, paged=True)
        run_trace(eng, n=4)
        s = eng.shard_stats()
        d, m = mesh_shape
        assert tuple(s["mesh_shape"]) == mesh_shape
        assert s["n_devices"] == d * m
        assert len(s["per_shard_pages_used"]) == d
        assert s["per_shard_pages_total"] * d == eng.n_pages
        assert s["per_shard_lora_slot_bytes"] > 0
        # Gauges flow into the metrics surface for cluster merging.
        assert eng.metrics().sched_stats["n_devices"] == d * m

    @pytest.mark.parametrize("mesh_shape", MESHES[1:])
    def test_pool_accounting_mesh_invariant(self, small_model,
                                            mesh_shape):
        """Global page/slot accounting must not depend on the mesh —
        only the per-shard view divides by the data-axis size."""
        a = make_engine(small_model, None, paged=True)
        b = make_engine(small_model, mesh_shape, paged=True)
        assert b.pool.snapshot()["capacity"] == \
            a.pool.snapshot()["capacity"]
        # Pool telemetry sizes per *device* (mesh.size), while pages
        # physically shard over the data axis only.
        assert b.pool.n_shards == mesh_shape[0] * mesh_shape[1]
        # Physical pages round up to the data axis; logical capacity
        # (hence every control-plane decision) stays mesh-invariant.
        assert b.n_pages % mesh_shape[0] == 0


# ------------------------------------------------------ mesh construction
class TestMakeServingMesh:
    @needs(2)
    def test_shapes_and_axes(self):
        mesh = make_serving_mesh(2, 1)
        assert mesh.axis_names == ("data", "model")
        assert dict(mesh.shape) == {"data": 2, "model": 1}
        mesh = make_serving_mesh(2, 2)
        assert dict(mesh.shape) == {"data": 1, "model": 2}

    def test_rejects_bad_arguments(self):
        with pytest.raises(ValueError, match="positive"):
            make_serving_mesh(0, 1)
        with pytest.raises(ValueError, match="positive"):
            make_serving_mesh(2, 0)
        with pytest.raises(ValueError, match="divide"):
            make_serving_mesh(3, 2)

    def test_rejects_too_many_devices(self):
        n = len(jax.devices())
        with pytest.raises(ValueError, match="XLA_FLAGS"):
            make_serving_mesh(2 * n, 1)


# ------------------------------------------------------------- fit_spec
class TestFitSpecWarnOnce:
    @needs(2)
    def test_warns_once_per_tensor(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import fit_spec
        mesh = make_serving_mesh(2, 2)
        shape, spec = (3, 8), P("model", None)   # 3 % 2 != 0 -> dropped
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            first = fit_spec(shape, spec, mesh, warn_label="w_odd")
            again = fit_spec(shape, spec, mesh, warn_label="w_odd")
        assert first == P() and again == P()
        msgs = [str(x.message) for x in w if "w_odd" in str(x.message)]
        assert len(msgs) == 1, "fit_spec should warn once per tensor"
        assert "replicated" in msgs[0]

    @needs(2)
    def test_silent_without_label(self):
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import fit_spec
        mesh = make_serving_mesh(2, 2)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fit_spec((5, 8), P("model", None), mesh)
        assert not [x for x in w if "fit_spec" in str(x.message)]


# ----------------------------------------------------- cluster / systems
class TestClusterAndSystems:
    def test_cluster_rejects_overcommitted_devices(self, small_model):
        from repro.serving.cluster import EngineCluster, \
            EngineClusterConfig
        cfg, params = small_model
        n = len(jax.devices())
        with pytest.raises(ValueError, match="devices"):
            EngineCluster(cfg, params,
                          ecfg=EngineConfig(**BASE, mesh_shape=(2, 1)),
                          ccfg=EngineClusterConfig(n_engines=n))

    @needs(2)
    def test_cluster_of_sharded_engines(self, small_model):
        from repro.serving.cluster import EngineCluster, \
            EngineClusterConfig
        cfg, params = small_model
        cluster = EngineCluster(cfg, params,
                                ecfg=EngineConfig(**BASE,
                                                  mesh_shape=(1, 2)),
                                ccfg=EngineClusterConfig(n_engines=1))
        eng = cluster.engines[0]
        assert eng.mesh is not None and eng.mesh.size == 2

    @needs(2)
    def test_build_system_threads_mesh_shape(self, small_model):
        from repro.serving.systems import build_system
        cfg, params = small_model
        eng = build_system("chameleon", "engine", model_cfg=cfg,
                           params=params, ecfg=EngineConfig(**BASE),
                           mesh_shape=(1, 2))
        assert dict(eng.mesh.shape) == {"data": 1, "model": 2}

    def test_build_system_rejects_mesh_on_sim_tier(self):
        from repro.serving.systems import build_system
        with pytest.raises(ValueError, match="mesh"):
            build_system("chameleon", "sim", mesh_shape=(1, 2))
