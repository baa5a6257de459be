"""The main path compiled for a described TPU v5e (on-chip-measurement §2).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what the chip's compiler refuses (a block not
aligned to the tiling, too much VMEM, a program that does not fit HBM)
fails here at no chip time.  Nothing runs, so these say nothing about
results or times; ``chip_smoke.py`` on the chip does.

The topology is described in a module fixture, never at import: only one
process may load libtpu, and the test workers import every test file.
"""

import base64
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # The TPU compiler's threads start with libtpu and inherit this
    # thread's CPU mask: keep them on one core, so the compiles do not
    # starve the other test workers' timing-sensitive heartbeats.
    cpus = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {max(cpus)})
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - no libtpu here
                pytest.skip(
                    f"no v5e:2x2 topology can be described here: {e}")
            # a described compile cannot be read back from the
            # persistent cache without a chip: keep it out
            jax.config.update("jax_enable_compilation_cache", False)
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        os.sched_setaffinity(0, cpus)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the compiled program"
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit"
    return compiled


def _grad_of(fn, n_args):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(n_args)))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(16, 512, 16, 64, 64),
                                   (2, 4096, 16, 64, 64),
                                   (2, 4096, 16, 192, 128)],
                         ids=["seq512", "seq4096", "mla4096"])
def test_flash_compiles(one_chip, shape, direction):
    """(B, S, heads, q/k width, v width); the last is Moonlight's latent
    attention as one chip of its four-chip cell holds it.  bf16 inputs,
    so the kernels' matmuls take bf16 operands."""
    from zhpe_ompi_tpu.ops.flash_attention import _flash

    B, S, h, hd, dv = shape
    block_q, block_k = min(512, S), min(1024, S)  # flash_attention's

    def fwd(q, k, v):
        return _flash(q, k, v, True, block_q, block_k, False)

    fn = fwd if direction == "fwd" else _grad_of(fwd, 3)
    q = jax.ShapeDtypeStruct((B, S, h, hd), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, S, h, dv), jnp.bfloat16, sharding=one_chip)
    _compile_for_chip(jax.jit(fn).lower(q, q, v))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_layernorm_compiles(one_chip, direction):
    from zhpe_ompi_tpu.ops.fused_norm import _ln_pallas

    def fwd(x, g):
        return _ln_pallas(x, g, 256, False)

    fn = fwd if direction == "fwd" else _grad_of(fwd, 2)
    x = jax.ShapeDtypeStruct((8192, 1024), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=one_chip)
    _compile_for_chip(jax.jit(fn).lower(x, g))


def test_train_step_compiles(one_chip, monkeypatch):
    """The AdamW train step the cells run, at a 4-layer cut of the chip
    width on one chip, with the dispatchers steered to their TPU branch
    (they ask ``jax.devices()``, which here is the CPU)."""
    import optax

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm
    from zhpe_ompi_tpu.ops import flash_attention, fused_norm

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fused_norm, "on_tpu", lambda: True)
    cfg = tfm.Config(vocab=8192, d_model=1024, n_heads=16, d_ff=4096,
                     n_layers=4, seq=512, dtype=jnp.bfloat16, remat=True,
                     ce_chunk=1024)
    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1),
                ("dp", "tp"))
    dp_comm = zmpi.Communicator(mesh, "dp", name="aot_dp")
    init_opt, step, specs = tfm.make_train_step_optax(
        cfg, mesh, dp_comm, None, optimizer=optax.adamw(1e-3))
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    params = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                sharding=NamedSharding(mesh, specs[k]))
        for k, v in shapes.items()
    }
    opt = jax.eval_shape(init_opt, params)
    tok = jax.ShapeDtypeStruct((16, cfg.seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp")))
    _compile_for_chip(jax.jit(step).lower(params, opt, tok, tok))


# ------------------------------------------------- names a profile reads

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "layernorm_fwd",
           "layernorm_bwd")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start)?$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r', metadata=\{(?:[^}"]|"[^"]*")*\}'
                       r"|^(?:FileNames|FunctionNames|FileLocations|"
                       r"StackFrames)\n(?:.+\n)*", re.M)


def _compile_optax_step(topo, monkeypatch):
    """The flagship AdamW step (gradient and update programs as one jit) at
    a small size over a dp2 x tp2 mesh of the described chips, kernels on;
    returns the optimized HLO text."""
    import optax

    import zhpe_ompi_tpu as zmpi
    from zhpe_ompi_tpu.models import transformer as tfm
    from zhpe_ompi_tpu.ops import flash_attention, fused_norm

    monkeypatch.setattr(flash_attention, "on_tpu", lambda: True)
    monkeypatch.setattr(fused_norm, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("dp", "tp"))
    cfg = tfm.Config(vocab=512, d_model=256, n_heads=2, d_ff=512,
                     n_layers=2, seq=128, remat=True, ce_chunk=256)
    dp_comm = zmpi.Communicator(mesh, "dp", name="t_dp")
    tp_comm = zmpi.Communicator(mesh, "tp", name="t_tp")
    init_opt, step, specs = tfm.make_train_step_optax(
        cfg, mesh, dp_comm, tp_comm, optimizer=optax.adamw(1e-3))
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    params = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                sharding=NamedSharding(mesh, specs[k]))
        for k, v in shapes.items()
    }
    opt = jax.eval_shape(init_opt, params)
    tok = jax.ShapeDtypeStruct((4, cfg.seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("dp")))
    return jax.jit(step).lower(params, opt, tok, tok).compile().as_text()


def _ops(text):
    """(instruction name, opcode, op_name) of each instruction."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), n.group(1) if n else ""))
    return out


@pytest.fixture(scope="module")
def optax_step_hlo(topo):
    with pytest.MonkeyPatch.context() as mp:
        return _compile_optax_step(topo, mp)


@pytest.mark.parametrize("scope", ["zmpi.attn", "zmpi.mlp", "zmpi.loss"])
def test_model_scopes_name_forward_and_backward(optax_step_hlo, scope):
    # a scope reads "zmpi.attn/..." or, under a transform of the whole
    # scope, "transpose(jvp(zmpi.loss))/..."
    names = [n for _, _, n in _ops(optax_step_hlo)
             if re.search(re.escape(scope) + r"[/)]", n)]
    assert any("transpose(" not in n for n in names), f"no forward {scope}"
    assert any("transpose(" in n for n in names), f"no backward {scope}"


def test_optimizer_scope_names_the_update(optax_step_hlo):
    assert any("zmpi.optimizer/" in n for _, _, n in _ops(optax_step_hlo))


def test_every_collective_names_its_communicator(optax_step_hlo):
    """Each collective carries ``zmpi.coll.<axis>.<name>.<op>``, and the
    axis is the one its replica groups run over (a collective permute's
    pairs, each within one group of the axis)."""
    groups = {"tp": "{{0,1},{2,3}}", "dp": "{{0,2},{1,3}}"}
    lines = {m.group(1): line for line in optax_step_hlo.splitlines()
             if (m := _INSTR.match(line))}
    axes = set()
    for instr, op, name in _ops(optax_step_hlo):
        if not _COLLECTIVE.match(op):
            continue
        m = re.search(r"zmpi\.coll\.(\w+)\.t_\1\.allreduce/", name)
        assert m, f"{instr} carries no communicator scope: {name}"
        axis = m.group(1)
        if op.startswith("collective-permute"):
            pairs = re.search(r"source_target_pairs=\{(.*?)\}\}",
                              lines[instr]).group(1)
            within = [set(map(int, g.split(","))) for g in
                      re.findall(r"\{([\d,]+)\}", groups[axis])]
            for s, t in re.findall(r"\{(\d+),(\d+)", pairs):
                assert any({int(s), int(t)} <= g for g in within), instr
        else:
            assert f"replica_groups={groups[axis]}" in lines[instr]
        axes.add(axis)
    assert axes == {"dp", "tp"}


def test_kernels_are_named(optax_step_hlo):
    """Every Pallas call is an instruction named for its kernel, the name
    a profile's op events carry."""
    found = []
    for line in optax_step_hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            instr = _INSTR.match(line).group(1)
            hits = [k for k in KERNELS if k in instr]
            assert len(hits) == 1, f"kernel instruction {instr}"
            found.append(hits[0])
    assert set(found) == set(KERNELS)


def test_scopes_change_only_metadata(topo, optax_step_hlo, monkeypatch):
    """Without the scopes the compiled step is the same program: the same
    instructions, fusions, layouts, order and kernels, once the metadata
    is dropped (with the source tables it points into) and the
    instructions are renamed in order of appearance."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compile_optax_step(topo, monkeypatch)
    assert "zmpi." not in bare and "zmpi." in optax_step_hlo

    from jaxlib.mlir import ir

    def kernel(m):
        # a kernel's body is Mosaic bytecode that carries its own source
        # locations, the scopes among them: compare it without them
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        body = ir.Module.parse(base64.b64decode(m.group(1)), context=ctx)
        return body.operation.get_asm(enable_debug_info=False)

    def structure(text):
        text = _METADATA.sub("", text)
        text = re.sub(r'"body":"([^"]*)"', kernel, text)
        names = {}
        return re.sub(r"%[\w.-]+",
                      lambda m: names.setdefault(m.group(0),
                                                 f"%{len(names)}"), text)

    assert structure(bare) == structure(optax_step_hlo)


_COMPUTE = re.compile(r" (?:fusion|convolution|custom-call)\(")


def _computations(text):
    """Each computation's instruction lines, in their scheduled order."""
    comps, cur = [], None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            cur = []
            comps.append(cur)
        elif cur is not None and _INSTR.match(line):
            cur.append(line)
    return comps


def test_tp_all_reduces_overlap_the_other_chunk(optax_step_hlo):
    """The forward layer body issues four tp all-reduces, one per chunk
    and layer part, each its own asynchronous transfer; and at least
    three of them have the other chunk's kernels or matmuls scheduled
    between their start and their done (the last one, at the body's end,
    has nothing left beside it)."""
    def tp_forward(line):
        n = _OP_NAME.search(line)
        return n and "zmpi.coll.tp." in n.group(1) \
            and "transpose(" not in n.group(1)

    (body,) = [c for c in _computations(optax_step_hlo)
               if any(_COLLECTIVE.match(_INSTR.match(ln).group(2))
                      and tp_forward(ln) and "/while/body/" in ln
                      for ln in c)]
    ops = [_INSTR.match(ln).groups() for ln in body]
    starts = [i for i, (_, op) in enumerate(ops)
              if _COLLECTIVE.match(op) and tp_forward(body[i])]
    assert len(starts) == 4, [ops[i] for i in starts]
    hidden = 0
    for i in starts:
        name, op = ops[i]
        assert op.endswith("-start"), f"{name} is a blocking {op}"
        (done,) = [j for j, ln in enumerate(body)
                   if re.search(rf"-done\(%{re.escape(name)}\)", ln)]
        hidden += any(_COMPUTE.search(ln) and "kind=kLoop" not in ln
                      for ln in body[i + 1:done])
    assert hidden >= 3
