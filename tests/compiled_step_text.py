"""What a program compiled for a described TPU v5e (no chip) is read by, for every test file that
compiles one: the fixtures (`topo`, `one_chip`, `on_tpu`), a family cell's whole step made of shapes
(`cell_config`, `lower_cell_step`), and the readers of a compiled program's text. A file imports what
it uses; pytest does not collect this module (its name).

`tests/conftest.py` turns XLA's optimiser off for the process: its CPU programs run once on a few dozen
tokens and are held to a reference's arithmetic, not to XLA's CPU code generator. A program compiled
for the described chip is read for its OPTIMISED text and its memory figure, so `tpu_side` (the
`on_tpu` fixture) turns the optimiser back on for what is compiled inside it, and a file of CPU
programs that are held to the optimiser's own bits keeps it (`optimised`). `jax.jit`'s caches do
not key on that setting (`jax_disable_most_optimizations` is not part of the jit key): they are
cleared where it flips, so that no program compiled under one setting is handed to a test under the other.

Also a script. Is a change metadata only? Each family cell's train step compiled at the cell's own
size, and two trees' compiled texts held against each other with what names and places leave in them
taken out: every `metadata={...}`, the stack frames' tables, and the source locations inside the
Mosaic kernels' bodies. PR 52 (named scopes) showed its six steps identical to the parent's this way,
`memory_analysis()` equal to the byte.

    python tests/compiled_step_text.py compile TREE OUT [config ...]   (~1 min a step, ~12 GB)
    python tests/compiled_step_text.py compare OUT_A OUT_B

`compile` runs TREE's own code (a `git archive` of the parent, or `.`). One process at a time may
load the TPU compiler unless ALLOW_MULTIPLE_LIBTPU_LOAD=1, which the driver's tier-1 command sets
(`/root/TESTS_LAST_RUN.json`) and no file of the repository does: under several xdist workers
without it, every worker but the first to describe the chip SKIPS its files' compiles (`topo`).
"""
import base64
import contextlib
import difflib
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


# ------------------------------------------------------------------- the described chip

def describe_v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@contextlib.contextmanager
def optimiser_on():
    """XLA's optimiser, which tests/conftest.py turns off for the process, on for what is compiled inside.
    `jax.jit`'s caches are cleared where the setting flips: they do not key on it."""
    if not jax.config.read("jax_disable_most_optimizations"):  # (a script, or inside another)
        yield
        return
    jax.config.update("jax_disable_most_optimizations", False)
    jax.clear_caches()
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", True)
        jax.clear_caches()


@contextlib.contextmanager
def tpu_side():
    """What is compiled inside is compiled as the chip's users compile it: the code's own backend probes
    steered to their TPU side (they ask the attached backend, which here is the CPU), XLA's optimiser on,
    and the products at the precision nobody set (a family's file compares at `highest`, which Mosaic
    refuses of bfloat16 operands)."""
    from ray_tpu.ops import flash_attention as fa

    was = fa._interpret, jax.default_backend
    fa._interpret, jax.default_backend = (lambda: False), (lambda: "tpu")
    try:
        with optimiser_on(), jax.default_matmul_precision(None):
            yield
    finally:
        fa._interpret, jax.default_backend = was


@pytest.fixture(scope="module")
def optimised():
    """For a file whose CPU programs are held to the bit, or to a rounding, of what XLA's optimiser makes
    of them (`pytestmark = pytest.mark.usefixtures("optimised")`): the file keeps the optimiser."""
    with optimiser_on():
        yield


@pytest.fixture(scope="module")
def topo():
    try:
        return describe_v5e()
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu():
    with tpu_side():
        yield


def shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def scalar(sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


def cell_config(name, directory="configs"):
    """(a configuration file of the benchmark, the program's model keys, its ModelConfig)"""
    if ROOT not in sys.path:  # benchmarks/ is read as its users read it, from the repository's root
        sys.path.insert(0, ROOT)
    from benchmarks.lib import modelcfg

    with open(os.path.join(ROOT, "benchmarks", directory, f"{name}.json")) as f:
        config = json.load(f)
    model = modelcfg.model_keys(config)
    return config, model, modelcfg.model_config(model)


def glm_share():
    """A chip's share of the GLM-4.7-Flash cell's expert layer (8 of 64 experts held), written by hand."""
    from ray_tpu.models.config import ModelConfig

    return ModelConfig(
        name="glm-shape", vocab_size=19360, d_model=2048, n_layers=5, n_heads=20, n_kv_heads=20,
        d_ff=10240, n_experts=64, moe_top_k=4, moe_capacity_factor=0.0, d_ff_expert=1536,
        n_shared_experts=1, moe_scoring="sigmoid", moe_route_scale=1.8, moe_select_bias=True,
        experts_held=(0, 8))


def nemotron_share():
    """A chip's share of the Nemotron-3-Super cell (a period of its pattern, 8 of 512 experts held), written by hand."""
    from ray_tpu.models.config import ModelConfig

    return ModelConfig(
        name="nemotron-shape", vocab_size=16384, d_model=4096, n_layers=11, n_heads=32, n_kv_heads=2,
        d_ff=2688, layer_pattern="MEMEMEM*EME", ssm_n_heads=16, ssm_head_dim=64, ssm_n_groups=1,
        ssm_state=128, ssm_chunk=128, attn_heads_held=(4, 1), attention_rotation=False, n_experts=512,
        moe_top_k=22, moe_capacity_factor=0.0, d_ff_expert=2688, n_shared_experts=1, d_ff_shared=5376,
        moe_latent_dim=1024, mlp_activation="relu2", moe_scoring="sigmoid", moe_route_scale=5.0,
        moe_select_bias=True, experts_held=(0, 64))


def lower_cell_step(cfg, trainer, sharding):
    """A cell's train step as its configuration file's `trainer` states it, lowered for `sharding` from
    shapes alone (the batch a loader of the objective makes): (the step, its lowering, the shapes). Under `tpu_side`."""
    from ray_tpu.models import llama
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    tx = make_optimizer(**trainer["optimizer"])
    params = shapes(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)), sharding)
    state = TrainState(step=scalar(sharding), params=params, opt_state=shapes(jax.eval_shape(tx.init, params), sharding))
    b, n = trainer["batch"], trainer["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((b, n + 1), jnp.int32, sharding=sharding)}
    if cfg.diffusion_block:
        batch = {"tokens": jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=sharding),
                 "masked": jax.ShapeDtypeStruct((b, n), jnp.bool_, sharding=sharding),
                 "p_mask": jax.ShapeDtypeStruct((b,), jnp.float32, sharding=sharding)}
    step = make_train_step(cfg, tx)
    return step, step.lower(state, batch), (state, batch)


# ------------------------------------------------------------------- what a compiled program's text is read by

def pallas_grids(jaxpr):
    """The grid of every Pallas kernel in a program, nested calls included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += pallas_grids(sub)
    return grids

def xla_remats(text):
    """The instructions XLA made again by itself to fit the program (`.remat` in their names)."""
    return re.findall(r"^\s*(?:ROOT )?%[\w.\-]*\.remat\S*", text, re.M)

def instructions(text, op, scope=None):
    """The program's instructions of kind `op`, fused or not (under `scope`, by `op_name`)."""
    return [ln for ln in text.splitlines() if re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = .*?[\])}}] {op}\(", ln)
            and (scope is None or re.search(rf'op_name="[^"]*/{scope}/', ln))]

# a recurrent mixer's pattern character -> (the scope of its input product, the product's einsum, the
# kept result at its cell's shape in whichever order of its extents): `[B | C | x]` of the LFM2 cell,
# `[z | xBC | dt]` of the Nemotron cell, q | k | v before the convolution of the Solar-Open2 cell
KEPT_PRODUCTS = {
    "C": ("sconv_in_proj", "btd,dpe->btpe", r"bf16\[(?:4,8192,3,2048|3,4,8192,2048|4,3,8192,2048)\]"),
    "M": ("ssm_in_proj", "btd,de->bte", r"bf16\[(?:1,)?8192,2320\]"),
    "K": ("kda_in_proj", "btd,dphk->btphk", r"bf16\[(?:1,)?8192,(?:3072|3,8,128|12288|3,32,128)\]"),
}

def products(text, scope, einsum):
    """The compiled program's products (XLA's `convolution`) of `einsum` under `scope`, by what ran
    them: forward, the forward made again by a rematerialised layer, backward."""
    products = [ln for ln in instructions(text, "convolution", scope) if f"/{scope}/{einsum}/" in ln]
    again = sum("rematted_computation" in ln for ln in products)
    backward = sum("transpose(jvp(" in ln for ln in products) - again
    return len(products) - again - backward, again, backward

def _lines_by_fusion(text):
    """A compiled text's lines, each with whether it lies in a fused computation's body."""
    in_fusion = False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", ln)
        if head:
            in_fusion = head.group(1).startswith("fused_computation")
        yield ln, in_fusion

def kept_copies(text, extents):
    """(arrays with the `extents` of a mixer's kept product that the program makes and stores: results
    of fusions, products, copies and transposes outside fused computations (bitcasts, a loop's tuple
    plumbing and the asynchronous copies between fast memory and HBM, which change no layout, apart);
    how many of them the named residual's `reduce-precision` is fused behind the product itself).
    Equal, and one a part: ONE copy, rounded where the product wrote it, no pass of its own, none
    transposed."""
    stored, fused = 0, 0
    for ln, in_fusion in _lines_by_fusion(text):
        made = re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = {extents}\S* ([\w\-]+)\((%[\w.\-]+)", ln)
        if not made:
            continue
        if made.group(1) == "reduce-precision":
            assert in_fusion and made.group(2).startswith("%convolution"), ln  # not stand-alone
            fused += 1
        elif not in_fusion and made.group(1) in ("fusion", "convolution", "copy", "transpose"):
            stored += 1
    return stored, fused

def kernel_calls(text, name):
    """The compiled program's calls of the Pallas kernel `name`, by what ran them: forward,
    the forward made again by a rematerialised layer, backward."""
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and f"/{name}/" in ln]
    again = sum("rematted_computation" in ln for ln in calls)
    return len(calls) - again, again

# q|k|v `[1, 8192, 3072]` in float32 with the taps' 3 rows of zeros in front: `ssm._causal_conv`'s copy
CONV_PADDED_COPY = r"f32\[1,8195,(3072|12288)\]"

# float32 arrays of every chunk with the extents of a sub-chunk's differences [.., 32, 32, 128]
# or of the sub-chunks' factors [.., 4, 128, 128]: what `_decayed_overlaps` wrote to HBM
OVERLAPS_INTERMEDIATES = r"f32\[(\d+,)+(32,32,128|4,128,128)\]"

# float32 arrays of every chunk with the extents of `_chunk_parts`' right-hand sides and solutions,
# beta [k exp G | v] and [W | U0], [.., 128, 256]: what the second half wrote to HBM before its kernels
PARTS_INTERMEDIATES = r"f32\[(\d+,)+128,256\]"


# the delta-rule scan's output o and its cotangent at the cells' shapes, [1, 8192, H, 128] cut in 64 chunks of 128 in
# whichever grouping of its extents: what `kda._walk`'s `transpose` stored, forward, made again and backward
WALK_OUTPUT = r"f32\[(?:1,)?(?:(?:8192|64,128),(?:8|32)|(?:8|32),(?:8192|64,128)),128\]"

def stored_alone(text, extents, scope):
    """The `copy` and `transpose` instructions under `scope` that store an array of `extents` in a pass over
    HBM of their own: outside fused computations (inside one a `copy` is the fusion's read in another order)."""
    made = re.compile(rf"\s*(?:ROOT )?%[\w.\-]+ = {extents}\S* (?:copy|transpose)\(")
    return [ln for ln, in_fusion in _lines_by_fusion(text)
            if not in_fusion and made.match(ln) and re.search(rf'op_name="[^"]*/{scope}/', ln)]


# ------------------------------------------------------------------- two trees' steps, compared

CONFIGS = ("glm-4.7-flash-train-ep8", "nemotron-3-super-train-tp8-ep64", "solar-open2-train-tp8-ep40",
           "lfm2-24b-a2b-train-ep8", "trinity-mini-train-ep16", "sdar-30b-a3b-train-ep8",
           "kimi-linear-48b-a3b-train-ep32")


def compile_steps(tree: str, out: str, configs) -> None:
    global ROOT
    jax.config.update("jax_platforms", "cpu")  # (what backend selection reads, as tests/conftest.py)
    ROOT, out = os.path.abspath(tree), os.path.abspath(out)  # TREE's code and TREE's configuration files
    os.makedirs(out, exist_ok=True)
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import flash_attention as fa

    assert fa.__file__.startswith(ROOT), fa.__file__
    one = SingleDeviceSharding(describe_v5e().devices[0])
    with tpu_side():
        for config in configs:
            file, _, cfg = cell_config(config)
            compiled = lower_cell_step(cfg, file["trainer"], one)[1].compile()
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
