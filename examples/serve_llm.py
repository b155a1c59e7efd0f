"""Serve a (randomly initialized) LLM with continuous batching.

Demonstrates the serving stack end to end: a deployment wrapping the
continuous-batching LLMEngine, HTTP ingress, and concurrent requests
sharing decode ticks.

    python examples/serve_llm.py
"""

import json
import os
import threading
import urllib.request

# Hard-set (not setdefault): this demo serves a tiny random-weight model
# — it must not grab (or fail to share) a real TPU chip another process
# holds. The engine on the chip runs as `python chip_smoke.py`.
os.environ["JAX_PLATFORMS"] = "cpu"

import ray_tpu as ray
from ray_tpu import serve
from ray_tpu.models import configs

PORT = 18260


def main():
    ray.init(num_cpus=2, num_tpus=0)

    # The shared system prompt every request starts with: registered
    # once per replica, its prefill cost is paid once (prefix caching);
    # auto_prefix_min_hits would capture it automatically instead.
    SYSTEM_PROMPT = list(range(1, 17))

    @serve.deployment
    class Llm:
        def __init__(self):
            from ray_tpu.serve.llm import LLMServer

            self.server = LLMServer(configs.tiny_test(), num_slots=4,
                                    max_seq_len=128)
            self.server.register_prefix(SYSTEM_PROMPT)

        def __call__(self, payload):
            out = self.server.generate(
                SYSTEM_PROMPT + payload["prompt"],
                max_new_tokens=payload.get("max_tokens", 16))
            st = self.server.stats()
            return {"tokens": out["tokens"],
                    "ttft_ms": round(out["ttft_s"] * 1e3, 1),
                    "prefix_hits": st["prefix_hits"]}

    serve.run(Llm.bind(), name="llm", http=True, http_port=PORT)

    def ask(prompt):
        req = urllib.request.Request(
            f"http://127.0.0.1:{PORT}/llm",
            data=json.dumps({"prompt": prompt}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.load(r)["result"]

    threads, results = [], []
    for i in range(4):  # concurrent requests share the decode batch
        t = threading.Thread(
            target=lambda i=i: results.append(ask([1 + i, 2, 3])))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    for r in results:
        print(f"{len(r['tokens'])} tokens, TTFT {r['ttft_ms']}ms")
    serve.shutdown()
    ray.shutdown()


if __name__ == "__main__":
    main()
