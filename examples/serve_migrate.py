"""Serving + live state migration: a serving session whose model weights and
KV cache migrate between environments mid-stream (the paper's migration as
elastic serving infrastructure — DESIGN.md §1).

    PYTHONPATH=src python examples/serve_migrate.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import ExecutionEnvironment, MigrationEngine, StateReducer
from repro.models import LM

cfg = get_config("recurrentgemma-9b", reduced=True)
lm = LM(cfg, max_seq=96)
params = lm.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, cfg.vocab_size)

# --- serve the prompt on the "edge" environment -----------------------
logits, cache = lm.prefill(params, {"tokens": toks}, cache_len=96)
tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
first = []
for _ in range(4):
    logits, cache = lm.decode_step(params, cache, {"token": tok})
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    first.append(int(tok[0, 0]))
print("tokens decoded on edge:", first)

# --- migrate the LIVE serving state to the "pod" environment ----------
edge = ExecutionEnvironment("edge")
pod = ExecutionEnvironment("pod")
edge.state.update({"params": params, "cache": cache, "last_tok": tok})
engine = MigrationEngine(StateReducer(codec="zstd"), bandwidth=5e9, latency=0.2)
res = engine.migrate(edge, pod, names={"params", "cache", "last_tok"})
print(f"migrated serving state: {res.nbytes/1e6:.2f} MB "
      f"(params+cache+cursor) in {res.seconds:.3f}s modeled")

# --- continue decoding on the pod: stream must be seamless ------------
# device arrays are restored as device arrays, so the pod decodes from them
# as they arrived
p_params, p_cache, p_tok = (pod.state["params"], pod.state["cache"],
                            pod.state["last_tok"])
cont_pod, cont_edge = [], []
tok_e = tok
for _ in range(4):
    logits_p, p_cache = lm.decode_step(p_params, p_cache, {"token": p_tok})
    p_tok = jnp.argmax(logits_p, -1)[:, None].astype(jnp.int32)
    cont_pod.append(int(p_tok[0, 0]))
    logits_e, cache = lm.decode_step(params, cache, {"token": tok_e})
    tok_e = jnp.argmax(logits_e, -1)[:, None].astype(jnp.int32)
    cont_edge.append(int(tok_e[0, 0]))

print("continuation on pod :", cont_pod)
print("continuation on edge:", cont_edge)
assert cont_pod == cont_edge, "migrated stream diverged!"
print("OK: decode stream identical after live migration")
