"""Cluster data plane over real engines: routing units + e2e parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Request
from repro.models import api
from repro.serving.cluster import (EngineCluster, EngineClusterConfig,
                                   Router)
from repro.serving.engine import AdapterCatalog, ChameleonEngine, EngineConfig
from repro.serving.trace import Trace, TraceConfig, downscale_for_engine


# ------------------------------------------------------------------
# Router units (no jax needed)
# ------------------------------------------------------------------
class TestRouter:
    def test_round_robin_cycles(self):
        r = Router("round_robin", 3)
        assert [r.route(0) for _ in range(6)] == [0, 1, 2, 0, 1, 2]

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError):
            Router("clairvoyant", 2)

    def test_least_loaded_picks_min(self):
        r = Router("least_loaded", 3)
        assert r.route(7, loads=[3.0, 0.5, 2.0]) == 1

    def test_least_loaded_requires_loads(self):
        with pytest.raises(ValueError):
            Router("least_loaded", 3).route(7)

    def test_affinity_follows_residency(self):
        """An adapter's requests stay on the replica that has it
        resident, even when another replica is (mildly) less loaded."""
        r = Router("adapter_affinity", 3)
        node = r.route(5, loads=[1.4, 1.0, 1.5],
                       resident=[True, False, False])
        assert node == 0

    def test_affinity_spills_when_target_saturated(self):
        """Least-loaded balancing kicks in once the affinity target
        exceeds the overload bound (dLoRA imbalance trap, bounded)."""
        r = Router("adapter_affinity", 3, overload_factor=1.5)
        assert r.route(5, loads=[9.0, 1.0, 5.0],
                       resident=[True, False, False]) == 1

    def test_affinity_sticky_hint_without_residency(self):
        r = Router("adapter_affinity", 3)
        first = r.route(5, loads=[1.0, 0.0, 1.0])
        again = r.route(5, loads=[1.0, 1.0, 1.0])
        assert first == 1 and again == 1

    def test_affinity_consistent_hash_without_load_feed(self):
        """No load signal at all: placement degrades to a consistent
        hash — deterministic across router instances."""
        a = Router("adapter_affinity", 4)
        b = Router("adapter_affinity", 4)
        picks_a = [a.route(aid) for aid in range(32)]
        picks_b = [b.route(aid) for aid in range(32)]
        assert picks_a == picks_b
        assert len(set(picks_a)) > 1          # not all on one node

    def test_hash_stability_under_node_add(self):
        """Rendezvous hashing: growing the cluster remaps only a
        fraction of adapters."""
        small, big = Router("adapter_affinity", 4), \
            Router("adapter_affinity", 5)
        moved = sum(small._hash_node(a) != big._hash_node(a)
                    for a in range(200))
        assert moved < 100      # ~1/5 expected; far below full remap


class TestReplicaDevices:
    """Replica i of a cluster owns devices [i*k, (i+1)*k)."""

    def test_one_device_each(self, monkeypatch):
        from repro.launch import mesh
        monkeypatch.setattr(mesh.jax, "devices", lambda: list("abcd"))
        assert mesh.replica_devices(4) == [["a"], ["b"], ["c"], ["d"]]
        assert mesh.replica_devices(2, (2, 1)) == [["a", "b"], ["c", "d"]]

    def test_too_few_devices_share_the_default(self, monkeypatch):
        from repro.launch import mesh
        monkeypatch.setattr(mesh.jax, "devices", lambda: ["a"])
        assert mesh.replica_devices(3) == [None, None, None]


# ------------------------------------------------------------------
# Real-engine cluster e2e
# ------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("chameleon-llama-7b").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def request_specs(n, seed=0, adapters=8):
    rng = np.random.default_rng(seed)
    return [(int(rng.integers(4, 30)), int(rng.integers(2, 16)),
             int(rng.integers(0, adapters))) for _ in range(n)]


ECFG = dict(max_slots=4, max_len=128, n_lora_slots=4, n_adapters=8)


class TestEngineCluster:
    def test_two_engine_drain_token_parity(self, small_model):
        """End-to-end: the same requests through 1 engine and through a
        2-engine cluster must decode the *same tokens* — replicas share
        the AdapterCatalog, so placement may change latency, never
        content."""
        cfg, params = small_model
        specs = request_specs(10, seed=3)

        eng = ChameleonEngine(cfg, params, EngineConfig(**ECFG))
        solo = [Request(input_len=i, output_len=o, adapter_id=a)
                for i, o, a in specs]
        for r in solo:
            eng.submit(r)
        eng.drain()

        cluster = EngineCluster(cfg, params, EngineConfig(**ECFG),
                                EngineClusterConfig(n_engines=2))
        dup = [Request(input_len=i, output_len=o, adapter_id=a)
               for i, o, a in specs]
        for r in dup:
            cluster.submit(r)
        cluster.drain()

        merged, per_node = cluster.metrics()
        assert merged.completed() == len(specs)
        assert sum(m.completed() for m in per_node) == len(specs)
        outputs = {}
        for e in cluster.engines:
            outputs.update(e.outputs)
        for a, b in zip(solo, dup):
            assert eng.outputs[a.req_id] == outputs[b.req_id], \
                (a.input_len, a.adapter_id)

    def test_catalog_shared_not_duplicated(self, small_model):
        cfg, params = small_model
        cluster = EngineCluster(cfg, params, EngineConfig(**ECFG),
                                EngineClusterConfig(n_engines=3))
        for e in cluster.engines:
            assert e.catalog is cluster.catalog
            assert e.host_adapters is cluster.catalog.weights

    def test_catalog_weights_live_in_host_memory(self, small_model):
        """Catalog adapters are numpy in host RAM, so a cache fill is a
        host-to-device copy and idle adapters take no device memory."""
        cfg, _ = small_model
        cat = AdapterCatalog(cfg, 3, 8)
        leaves = jax.tree.leaves(cat.weights)
        assert leaves and all(isinstance(x, np.ndarray) for x in leaves)

    def test_pinned_engine_matches_default_placement(self, small_model):
        """An engine pinned to an explicit device keeps its resident
        state committed there and decodes the same tokens as one with
        the default placement."""
        cfg, params = small_model
        dev = jax.devices()[-1]
        specs = request_specs(6, seed=5)
        outs = []
        for devices in (None, [dev]):
            eng = ChameleonEngine(cfg, params, EngineConfig(**ECFG),
                                  devices=devices)
            reqs = [Request(input_len=i, output_len=o, adapter_id=a)
                    for i, o, a in specs]
            for r in reqs:
                eng.submit(r)
            eng.drain()
            outs.append([eng.outputs[r.req_id] for r in reqs])
        assert outs[0] == outs[1]
        assert eng.params["embed/tok"].devices() == {dev}
        assert eng.kv_pages[0].devices() == {dev}
        assert jax.tree.leaves(eng.lora)[0].devices() == {dev}

    def test_affinity_routes_to_resident_replica(self, small_model):
        """Once adapter 0 is resident on the replica that served it,
        later adapter-0 requests keep landing there."""
        cfg, params = small_model
        cluster = EngineCluster(cfg, params, EngineConfig(**ECFG),
                                EngineClusterConfig(
                                    n_engines=2,
                                    policy="adapter_affinity"))
        first = cluster.submit(Request(input_len=8, output_len=2,
                                       adapter_id=0)).node
        cluster.drain()
        assert cluster.engines[first].cache.resident(0)
        for _ in range(3):
            handle = cluster.submit(Request(input_len=8, output_len=2,
                                            adapter_id=0))
            assert handle.node == first
            cluster.drain()

    def test_run_replays_arrivals_and_reports(self, small_model):
        cfg, params = small_model
        tcfg = TraceConfig(rps=8.0, duration_s=1.0, n_adapters=8, seed=0)
        reqs = [Request(input_len=12, output_len=4, adapter_id=i % 8,
                        arrival_time=0.05 * i) for i in range(8)]
        trace = downscale_for_engine(
            Trace(requests=reqs, config=tcfg), 8, 32, 8)
        cluster = EngineCluster(cfg, params, EngineConfig(**ECFG),
                                EngineClusterConfig(n_engines=2))
        merged, per_node = cluster.run(trace.requests)
        assert merged.completed() == len(reqs)
        assert merged.p99_ttft() > 0.0
        assert merged.cache_stats["hits"] + merged.cache_stats["misses"] > 0
        assert len(per_node) == 2