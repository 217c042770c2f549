"""Is a change metadata only? Each family cell's train step compiled for a described v5e (no
chip) at the cell's own size, and two trees' compiled texts held against each other with what
names and places leave in them taken out: every `metadata={...}`, the stack frames' tables, and
the source locations inside the Mosaic kernels' bodies. PR 52 (named scopes) showed its six
steps identical to the parent's this way, `memory_analysis()` equal to the byte.

    python tests/compiled_step_text.py compile TREE OUT [config ...]   (~1 min a step, ~12 GB)
    python tests/compiled_step_text.py compare OUT_A OUT_B

`compile` runs TREE's own code (a `git archive` of the parent, or `.`); one process at a time
may load the TPU compiler unless ALLOW_MULTIPLE_LIBTPU_LOAD=1. Not collected by pytest.
"""
import base64
import difflib
import hashlib
import json
import os
import re
import sys

CONFIGS = ("glm-4.7-flash-train-ep8", "nemotron-3-super-train-tp8-ep64", "solar-open2-train-tp8-ep40",
           "lfm2-24b-a2b-train-ep8", "trinity-mini-train-ep16", "sdar-30b-a3b-train-ep8",
           "kimi-linear-48b-a3b-train-ep32")


def compile_steps(tree: str, out: str, configs) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    tree, out = os.path.abspath(tree), os.path.abspath(out)
    os.makedirs(out, exist_ok=True)
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    os.chdir(tree)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import test_tpu_compile as T
    from ray_tpu.models import llama
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    assert fa.__file__.startswith(tree), fa.__file__
    fa._interpret = lambda: False  # as the `on_tpu` fixture: the code's backend probes to their TPU side
    jax.default_backend = lambda: "tpu"
    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for config in configs:
        cfg, file = T._cell_file(config)
        trainer = file["trainer"]
        tx = make_optimizer(**trainer["optimizer"])
        params = T._shapes(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)), one)  # noqa: B023
        state = TrainState(step=T._scalar(one), params=params, opt_state=T._shapes(jax.eval_shape(tx.init, params), one))
        b, n = trainer["batch"], trainer["seq"]
        batch = {"tokens": jax.ShapeDtypeStruct((b, n + 1), jnp.int32, sharding=one)}
        if cfg.diffusion_block:
            batch = {"tokens": jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=one),
                     "masked": jax.ShapeDtypeStruct((b, n), jnp.bool_, sharding=one),
                     "p_mask": jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one)}
        compiled = make_train_step(cfg, tx).lower(state, batch).compile()
        with open(os.path.join(out, f"{config}.txt"), "w") as f:
            f.write(compiled.as_text())
        m = compiled.memory_analysis()
        memory = {k: getattr(m, k) for k in ("argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
                                             "alias_size_in_bytes", "generated_code_size_in_bytes")}
        with open(os.path.join(out, f"{config}.memory.json"), "w") as f:
            json.dump(memory, f)
        print(config, memory, flush=True)


def _kernel_body(match) -> str:
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    tpu.register_dialect(ctx)
    with ctx:  # MLIR bytecode WITH locations (name stacks among them): printed without
        asm = ir.Module.parse(base64.b64decode(match.group(1))).operation.get_asm(enable_debug_info=False)
    return '"body":"<' + hashlib.sha256(asm.encode()).hexdigest() + '>"'


def bare(text: str) -> str:
    """A compiled program's text without where it was written."""
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_body, text)
    text = re.sub(r", metadata=\{[^{}]*\}", "", text)
    assert " metadata=" not in text
    head, sep, body = text.partition("\nFileNames\n")  # the tables the metadata's stack frames index
    return head + body[body.index("\n\n\n"):] if sep else text


def compare(a: str, b: str) -> int:
    different = 0
    for name in sorted(n for n in os.listdir(a) if n.endswith(".txt") and os.path.exists(os.path.join(b, n))):
        texts = [bare(open(os.path.join(d, name)).read()) for d in (a, b)]
        memory = [json.load(open(os.path.join(d, name[:-4] + ".memory.json"))) for d in (a, b)]
        same = texts[0] == texts[1] and memory[0] == memory[1]
        different += not same
        print(name[:-4], "identical" if same else "DIFFERENT", hashlib.sha256(texts[0].encode()).hexdigest()[:16],
              len(texts[0].splitlines()), "lines;", "memory", memory[0] if memory[0] == memory[1] else memory)
        for line in list(difflib.unified_diff(*(t.splitlines() for t in texts), lineterm="", n=0))[:40]:
            print("   ", line[:300])  # which instruction moved
    return different


if __name__ == "__main__":
    if sys.argv[1] == "compile":
        compile_steps(sys.argv[2], sys.argv[3], sys.argv[4:] or CONFIGS)
    else:
        sys.exit(compare(sys.argv[2], sys.argv[3]))
