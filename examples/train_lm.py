"""Train any assigned LM architecture end-to-end (reduced config on CPU).

The exact same model/step/sharding code lowers the full configs on the
512-chip production mesh in the dry-run; here we run a real optimization
loop with checkpoint/auto-resume on a mesh of up to 2 devices (virtual
host devices on the CPU).

Run:  PYTHONPATH=src python examples/train_lm.py [arch] [steps]
e.g.  PYTHONPATH=src python examples/train_lm.py mixtral-8x7b 30
"""

import sys

import jax

from repro.data import TokenPipeline
from repro.distributed.sharding import ShardingRules, named_sharding
from repro.launch.mesh import make_mesh
from repro.models.registry import get_config
from repro.models.transformer import LM
from repro.optim import adamw, warmup_cosine
from repro.train.steps import build_train_step, init_train_state

arch = sys.argv[1] if len(sys.argv) > 1 else "qwen2-0.5b"
steps = int(sys.argv[2]) if len(sys.argv) > 2 else 20

cfg = get_config(arch).reduced()
model = LM(cfg)
# 2 virtual devices when the platform is the CPU (the CPU backend alone
# reads this); on an accelerator the mesh spans up to 2 real chips
jax.config.update("jax_num_cpu_devices", 2)
mesh = make_mesh((min(2, len(jax.devices())), 1), ("data", "model"))
rules = ShardingRules.default()
print(f"{cfg.name}: {model.param_count()/1e6:.2f}M params, family={cfg.family}")

opt = adamw(warmup_cosine(3e-3, 5, steps))
step_fn = jax.jit(build_train_step(model, opt, mesh, rules, microbatches=2),
                  donate_argnums=0)
pipe = TokenPipeline(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)

with mesh:
    state = init_train_state(model, opt, jax.random.key(0))
    shard = named_sharding(("batch", "seq"), rules, mesh)
    for i in range(steps):
        batch = pipe.jax_batch(i, {"tokens": shard, "labels": shard})
        state, m = step_fn(state, batch)
        if (i + 1) % 5 == 0:
            print(f"step {i+1:3d}  loss={float(m['loss']):.4f}  "
                  f"lr={float(m['lr']):.2e}")
print("done")
