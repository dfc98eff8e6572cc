"""Online serving demo: continuous batching over a slot grid.

Builds a tiny decoder, starts the serving stack in-process (slot
scheduler + threaded HTTP frontend — the same pieces the `serving` task
type runs through the launcher), fires a burst of concurrent HTTP
requests with mixed prompt/output lengths, and prints each stream plus
the scheduler's tick trace — watch a slot freed by a short request get
re-admitted while longer requests are still decoding.

`python examples/serving_example.py fleet` runs the FLEET variant
instead (docs/Fleet.md): two replicas behind a router task — requests
go through the router's identical `/v1/generate`, then one replica is
killed and the survivor keeps serving (health ejection + failover).

`python examples/serving_example.py --spec` turns on SPECULATIVE
decoding (docs/Serving.md "Speculative decoding"): the n-gram
self-drafter proposes tokens per slot, one windowed program verifies
them, and the repeated-structure request in the burst lands multiple
tokens per tick — the printed trace shows the per-tick accepted
counts, and the streams are identical to the exact path.

`python examples/serving_example.py --tp` runs TENSOR-PARALLEL decode
(docs/Serving.md "Tensor-parallel decode"): the weights and the paged
KV pool shard across 2 (virtual, on CPU) devices, XLA inserts the TP
all-reduces from the placements, and the streams are identical to the
single-device run — the printout shows per-device vs global KV bytes.
"""

import http.client
import json
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_YARN_PLATFORM", os.environ.get("EXAMPLE_PLATFORM", "cpu"))
if "--tp" in sys.argv[1:] and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # Must land before the first jax call in this process: the tp demo
    # needs 2 devices; on the CPU platform that means virtual host
    # devices (the same switch the test rig's conftest flips).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    )


def main(spec: bool = False, tp: bool = False) -> None:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh, select_devices
    from tf_yarn_tpu.serving import ServingServer, SlotScheduler

    config = TransformerConfig.tiny(max_seq_len=64, scan_layers=False)
    model = Transformer(config)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )
    mesh = None
    if tp:
        # Tensor-parallel replica: weights placed by the logical-axis
        # rules, slot KV sharded by kv-heads — the serving task does
        # exactly this from ServingExperiment(mesh_spec=MeshSpec(tp=2)).
        from tf_yarn_tpu import inference

        mesh = build_mesh(MeshSpec(tp=2), select_devices(2))
        params = inference.shard_restored_params(model, params, mesh)
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16),
        mesh=mesh,
    )

    # Paged KV slots: a global pool of 8-token blocks — 11 blocks here
    # where two slots at full context would hold 17, with a prefix cache
    # sharing repeated prompt prefixes (docs/Serving.md "Paged KV &
    # prefix cache"). --spec adds speculative decoding: 3 n-gram drafts
    # per slot per tick, verified in one windowed program
    # (docs/Serving.md "Speculative decoding").
    scheduler = SlotScheduler(
        engine, params, max_slots=2,
        block_size=8, num_blocks=11,
        spec_k=3 if spec else 0,
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    stats0 = scheduler.stats()
    print(f"serving on {server.endpoint} (grid of {scheduler.max_slots} "
          f"paged slots, {stats0['kv_cache_hbm_bytes']} KV bytes"
          + (f", spec_k={scheduler.spec_k}" if spec else "")
          + (f", tp={stats0['tp_degree']}: "
             f"{stats0['kv_cache_hbm_bytes_per_device']} KV bytes/device"
             if tp else "") + ")")

    rng = np.random.RandomState(0)
    motif = rng.randint(0, 256, 3)
    bodies = [
        {"prompt": rng.randint(0, 256, 5).tolist(), "max_new_tokens": 3},
        # Repeated structure: with --spec the n-gram drafter reads the
        # motif and this request lands multiple tokens per tick.
        {"prompt": np.tile(motif, 3).tolist(), "max_new_tokens": 12},
        {"prompt": rng.randint(0, 256, 3).tolist(), "max_new_tokens": 6},
        {"prompt": rng.randint(0, 256, 7).tolist(), "max_new_tokens": 8},
    ]
    results = {}

    def call(index):
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=300
        )
        conn.request(
            "POST", "/v1/generate", json.dumps(bodies[index]),
            {"Content-Type": "application/json"},
        )
        results[index] = json.loads(conn.getresponse().read())
        conn.close()

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()

    for index, body in enumerate(bodies):
        reply = results[index]
        print(
            f"request {index}: P={len(body['prompt'])} "
            f"max_new={body['max_new_tokens']} -> {reply['tokens']} "
            f"({reply['finish_reason']}, ttft {reply['ttft_s']:.3f}s)"
        )

    print("\ntick trace (admit/retire interleaving = continuous batching):")
    for entry in scheduler.trace:
        if entry["admitted"] or entry["retired"]:
            print(f"  {entry}")
    if spec:
        accepted = [n for t in scheduler.trace
                    for n in t.get("accepted", {}).values()]
        stats = scheduler.stats()["spec"]
        print(f"\nspeculative: accept_rate={stats['accept_rate']}, "
              f"max tokens landed in one tick="
              f"{max(accepted) if accepted else 0}")

    server.stop()
    scheduler.close()


def fleet() -> None:
    """Two serving replicas behind a fleet router (docs/Fleet.md):
    discovery through the KV endpoint events, health-probed admission,
    least-loaded balancing, and kill-one-replica failover — all the
    pieces `fleet_topology` launches, in one process."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu import event
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet import ReplicaRegistry, RouterServer, make_policy
    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig
    from tf_yarn_tpu.serving import ServingServer, SlotScheduler

    config = TransformerConfig.tiny(max_seq_len=64, scan_layers=False)
    model = Transformer(config)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )
    # One engine shared by both replicas: compiles are paid once.
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
    )
    kv = InProcessKV()
    replicas = []
    for index in range(2):
        scheduler = SlotScheduler(engine, params, max_slots=2)
        scheduler.start()
        server = ServingServer(scheduler, "127.0.0.1", 0)
        server.start()
        task = f"serving:{index}"
        # The discovery protocol the launcher's serving tasks speak.
        event.serving_endpoint_event(kv, task, server.endpoint)
        replicas.append((task, scheduler, server))
        print(f"replica {task} on {server.endpoint}")

    registry = ReplicaRegistry(
        kv, tasks=[task for task, _, _ in replicas], probe_interval_s=0.2
    )
    registry.refresh(force=True)
    router = RouterServer(
        registry, make_policy("least_loaded"), "127.0.0.1", 0, retries=3
    )
    router.start()
    print(f"router on {router.endpoint} "
          f"({len(registry.healthy())} replicas healthy)")

    def ask(tag):
        rng = np.random.RandomState(hash(tag) % 2**16)
        body = {"prompt": rng.randint(0, 256, 5).tolist(),
                "max_new_tokens": 6}
        conn = http.client.HTTPConnection(
            "127.0.0.1", router.port, timeout=300
        )
        conn.request(
            "POST", "/v1/generate", json.dumps(body),
            {"Content-Type": "application/json"},
        )
        reply = json.loads(conn.getresponse().read())
        conn.close()
        print(f"  {tag}: {reply['tokens']} ({reply['finish_reason']})")

    print("\nfour requests through the router:")
    for index in range(4):
        ask(f"request {index}")
    print("routed:", router.stats()["routed_requests"])

    task0, scheduler0, server0 = replicas[0]
    print(f"\nkilling {task0} — the fleet keeps serving:")
    server0.stop()
    scheduler0.close()
    for index in range(3):
        ask(f"after-kill {index}")
    stats = router.stats()
    print("routed:", stats["routed_requests"])
    print("replica states:",
          {t: r["state"] for t, r in stats["replicas"].items()})

    router.stop()
    for _task, scheduler, server in replicas[1:]:
        server.stop()
        scheduler.close()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "fleet":
        fleet()
    else:
        main(spec="--spec" in sys.argv[1:], tp="--tp" in sys.argv[1:])
