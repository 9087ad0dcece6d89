"""Serve: HTTP deployments + a continuous-batching LLM with a paged KV cache.

Run on a CPU box:  JAX_PLATFORMS=cpu python examples/04_serve_llm.py
On a TPU host the same script binds the LLM replica to a chip: `ray.init()`
counts the chips (the driver itself never imports jax), `CHIPS` below picks
them up, and the replica runs in a worker spawned with `num_tpus=1`.
chip_smoke.py does this at llama_1b width.
"""
import http.client
import json

import ray_tpu as ray
from ray_tpu import serve
from ray_tpu.serve.llm import LLMConfig, LLMServer

ray.init(num_cpus=4)
CHIPS = int(ray.cluster_resources().get("TPU", 0))


@serve.deployment
class Hello:
    def __call__(self, request):
        name = request.query_params.get("name", "world")
        return {"hello": name}


# without num_tpus a replica is a CPU worker, whatever the host has
@serve.deployment(ray_actor_options={"num_tpus": 1} if CHIPS else {})
class Generate:
    def __init__(self):
        # paged=True: vLLM-style block-table KV cache; on TPU the decode
        # walks it with the pallas kernel in ops/paged_attention.py
        self.llm = LLMServer(LLMConfig(preset="tiny", max_batch_slots=4,
                                       max_seq_len=128, paged=True,
                                       page_size=16))

    async def __call__(self, request):
        body = request.json()
        out = await self.llm.generate(body["prompt_ids"],
                                      max_tokens=body.get("max_tokens", 16))
        return {"tokens": out["tokens"], "ttft_s": round(out["ttft_s"], 4)}


serve.run(Hello.bind(), name="hello", route_prefix="/hello")
serve.run(Generate.bind(), name="gen", route_prefix="/generate")
port = serve.start(http_options={"port": 0})

conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
conn.request("GET", "/hello?name=tpu")
print("hello:", conn.getresponse().read().decode())
conn.request("POST", "/generate",
             body=json.dumps({"prompt_ids": [1, 2, 3, 4], "max_tokens": 8}))
print("generate:", conn.getresponse().read().decode())
conn.close()

serve.shutdown()
ray.shutdown()
