"""Serve an LM with the continuous-batching decode engine.

Concurrent users stream shared-prefix prompts at an autoscaled LLM
deployment with the full serving tier on: seeded temperature/top-p
sampling, a prefix cache shared across replicas through a directory
actor, cache-affinity routing (generate_many groups prompts by prefix),
and speculative decoding with a layer-skip draft.  Prompts/completions
ride the object plane zero-copy (put_many/get_many).

Run: python examples/serve_llm.py

On a TPU host: CHIPS=1 python examples/serve_llm.py.  Each replica then
reserves one chip with ``ray_actor_options={"num_tpus": 1}`` and decodes
there; a replica that reserves no chip is a CPU worker.  ``stats()``
reports the platform the engine is on.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from a source tree
import numpy as np

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm_engine import LLMServer, generate_many
from ray_tpu.serve.prefix_cache import create_directory
from ray_tpu.serve.sampling import SamplingParams

if __name__ == "__main__":
    ray_tpu.init()
    # One directory actor shares published KV pages across every
    # replica; bind args carry its handle into each LLMServer.
    directory = create_directory()
    chips = int(os.environ.get("CHIPS", "0"))
    # A replica that reserves chips can only be added while some are free.
    max_replicas = 2 if not chips else max(1, min(2, int(
        ray_tpu.cluster_resources().get("TPU", 0)) // chips))
    dep = serve.deployment(
        LLMServer, name="llm",
        ray_actor_options={"num_tpus": chips} if chips else None,
        autoscaling_config={"min_replicas": 1, "max_replicas": max_replicas,
                            # Scale on engine load (active+queued work
                            # per decode slot), not router queue depth.
                            "metric_method": "autoscale_metric",
                            "target_num_ongoing_requests_per_replica": 1.0})
    handle = serve.run(dep.bind(
        "gpt2", {"tiny": True}, 0,
        # Speculative decoding: a 1-layer draft of the same family.
        draft_config_kw={"tiny": True, "num_layers": 1}, spec_tokens=4,
        prefix_cache=True, prefix_directory=directory,
        max_slots=8, page_size=16, max_ctx=128))

    # Shared-prefix workload: a 32-token "system prompt" + unique tails.
    rng = np.random.default_rng(0)
    system = list(map(int, rng.integers(0, 512, size=32)))
    prompts = [system + list(map(int, rng.integers(0, 512, size=int(n))))
               for n in rng.integers(4, 17, size=32)]
    # Per-request sampling: seeded, so outputs are reproducible.
    sampling = [SamplingParams(temperature=0.8, top_p=0.95, seed=i)
                for i in range(len(prompts))]
    outs = generate_many(handle, prompts, max_new_tokens=16,
                         sampling=sampling)
    print("generated", sum(len(o) for o in outs), "tokens for",
          len(outs), "requests; first:", outs[0][:8])

    # Streaming: chunks arrive while the request is still decoding.
    # Affinity routing keeps every call of the stream on ONE replica —
    # request ids are replica-local, and the shared prompt prefix means
    # that replica already holds the cached KV pages.
    from ray_tpu.serve.prefix_cache import affinity_key

    key = affinity_key(prompts[0])
    rid = ray_tpu.get(handle.method("submit_stream").remote(
        prompts[0], 32, None, SamplingParams(temperature=0.7, seed=7),
        _affinity=key))
    n = 0
    while True:
        chunk = ray_tpu.get(handle.method("next_chunk").remote(
            rid, _affinity=key))
        if chunk is None:
            break
        n += 1
        print("chunk", n, "->", chunk)

    stats = ray_tpu.get(handle.method("stats").remote())
    print("decoding on:", stats["platform"], stats["device_kind"])
    print("mid-batch admissions:", stats["admitted_mid_batch"],
          "avg occupancy:", round(stats["avg_batch_occupancy"], 2))
    print("prefix cache: hit pages", stats["prefix_hit_pages"],
          "prefill tokens saved", stats["prefill_tokens_saved"],
          "published", stats["prefix_published_pages"])
    print("speculative decode: acceptance",
          round(stats["spec_acceptance_rate"], 3),
          f"({stats['spec_accepted']}/{stats['spec_proposed']} draft"
          " tokens accepted)")
    print("router affinity:", handle.queue_stats()["affinity_hits"],
          "affinity-routed calls")
    serve.shutdown()
    ray_tpu.shutdown()
