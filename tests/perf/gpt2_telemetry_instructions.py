"""utils/hlo.instructions of a small GPT-2's compiled step programs (gradient and update program, and
the fused step) with telemetry.enabled on, lowered through the compile watchdog's proxies:
run it on two trees and compare the files (a PR that takes wiring out of TelemetrySession shows so that
what the session watches did not move): python tests/perf/gpt2_telemetry_instructions.py <root> <out.json>"""
import hashlib, json, os, sys
root = os.path.abspath(sys.argv[1])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, root)
os.chdir(root)
import numpy as np
import jax
import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.utils import hlo

cfg = gpt2.GPT2Config(vocab_size=512, n_positions=64, n_embd=64, n_layer=2, n_head=4)
model = gpt2.GPT2Model(cfg)
params = model.init(jax.random.PRNGKey(0))
out = {}
for fused in (False, True):
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params, config_params={
        "train_batch_size": 8, "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-5}}, "steps_per_print": 10 ** 9,
        "fused_step": fused,
        "telemetry": {"enabled": True, "output_path": os.path.splitext(os.path.abspath(sys.argv[2]))[0] + ".telemetry"}})
    tokens = np.arange(8 * 64, dtype=np.int32).reshape(8, 64) % 512
    for program, jitted, args, _ in engine.lint_programs((tokens, tokens)):
        instr = hlo.instructions(jitted.lower(*args).compile().as_text())
        out[f"{'fused' if fused else 'two-program'}/{program}"] = instr
        print(program, len(instr), hashlib.sha256("\n".join(instr).encode()).hexdigest()[:16], flush=True)
json.dump(out, open(sys.argv[2], "w"))
