"""The fleet's Pallas kernels compiled (not interpreted) for a described
TPU v5e chip, at the paper CNN's width.

Nothing runs: each test lowers a kernel with ``interpret=False`` against a
v5e topology that the installed TPU compiler can describe without a chip
attached, compiles it, and checks that the program holds a Mosaic
``tpu_custom_call`` named after its kernel (the `pallas_call`'s ``name``,
which a profile's op names carry).  This catches what interpret mode hides: block shapes
that do not tile, casts the chip has no instruction for, VMEM overruns.

The topology is described inside a module fixture, never at import, so
that every pytest-xdist worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.upload_fused import upload_fused_fleet
from repro.kernels.window_fold import window_fold_fleet
from repro.kernels.wire_bytes import nnz_fleet
from repro.models.cnn import cnn_flat_layout

COHORT = 64
# the `name=` each kernel's pallas_call gives its custom call
KERNEL_NAMES = {"upload_fused": "upload_fused", "window_fold": "window_fold",
                "nnz": "wire_bytes"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: a program
    compiled for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _calls(c, p, bounds):
    """name -> (kernel call with interpret=False, argument shapes/dtypes)."""
    f32, i32 = jnp.float32, jnp.int32
    return {
        "upload_fused": (
            lambda f, r, t, s, sc: upload_fused_fleet(
                f, r, t, s, sc, 0.05, 1.0, boundaries=bounds,
                need_nnz=True, interpret=False),
            [((c, p), f32), ((c, p), f32), ((c, len(bounds)), f32),
             ((c,), i32), ((c,), f32)]),
        "window_fold": (
            lambda pf, om, g, a, b: window_fold_fleet(pf, om, g, a, b,
                                                      interpret=False),
            [((p,), f32), ((c, p), f32), ((c,), i32), ((c,), f32),
             ((c,), f32)]),
        "nnz": (lambda f: nnz_fleet(f, interpret=False), [((c, p), f32)]),
    }


@pytest.mark.parametrize("name", ["upload_fused", "window_fold", "nnz"])
def test_fleet_kernel_compiles_for_v5e(one_chip, name):
    p, bounds = cnn_flat_layout((28, 28))
    assert len(bounds) == 6
    fn, arg_shapes = _calls(COHORT, p, bounds)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert re.search(rf"%{KERNEL_NAMES[name]}(\.\d+)? = .* custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text())


def test_onehot_selection_compiles_at_highest_for_v5e(one_chip):
    """Local SGD's one-hot minibatch product, vmapped over 1,000 nodes of
    60 28x28 images, keeps ``Precision.HIGHEST`` in the chip's program:
    at a lower precision the TPU would round the selected rows to bf16."""
    from repro.fleet.stages import select_rows
    x = jax.ShapeDtypeStruct((1000, 60, 28, 28, 1), jnp.float32,
                             sharding=one_chip)
    idx = jax.ShapeDtypeStruct((1000, 128), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.vmap(select_rows)).lower(x, idx).compile().as_text()
    products = [l for l in text.splitlines()
                if re.search(r"= f32\[1000,128,784\]\S* convolution\(", l)]
    assert products
    assert all("operand_precision={highest,highest}" in l for l in products)
    assert " gather(" not in text


def _cell_local_sgd(one_chip):
    """The 1,000-node cell's local SGD (60-row shards, B=128, 10 steps of
    the paper CNN) compiled for the described chip, as HLO text."""
    from repro.fleet import stages
    from repro.models.cnn import cnn_loss, init_cnn
    c, m = 1000, 60
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init_cnn, jax.random.PRNGKey(0)))
    keys = jax.eval_shape(lambda k: jax.random.split(k, c),
                          jax.random.PRNGKey(0))
    args = (params,
            jax.ShapeDtypeStruct((c, m, 28, 28, 1), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((c, m), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip))
    lt = stages.make_local_train(cnn_loss, 10, 0.1, 128)
    f = jax.jit(jax.vmap(lt, in_axes=(None, 0, 0, 0, 0)))
    return f.lower(*args).compile().as_text()


def _element_types(text, selection=False):
    """Each instruction of a compiled module that outputs floats, fused
    bodies included, outside the minibatch selection (or, with
    ``selection``, inside it), as (op_name, opcode, float element types
    of its output), counted; instructions with no op_name (the compiler's
    layout copies) count as ("", opcode, types).  Integer index
    arithmetic is left out: its op_names follow how the selection's
    indices are built."""
    out = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if m is None or m.group(2) in ("parameter", "get-tuple-element",
                                       "tuple", "bitcast", "constant"):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        name = name.group(1) if name else ""
        types = tuple(re.findall(r"\b(bf16|f16|f32|f64)\[", m.group(1)))
        if types and ("batch_select" in name) == selection:
            out[(name, m.group(2), types)] += 1
    return out


def test_local_sgd_keeps_the_gathers_element_types_for_v5e(one_chip,
                                                           monkeypatch):
    """Compiled for the chip at the 1,000-node cell's shape (60-row shards,
    B=128, 10 steps of the paper CNN), local SGD through the one-hot
    product writes the rows as f32, as the gather ``x[idx]`` does, and
    runs every op outside the selection at the element types it has
    through the gather: no intermediate of the CNN's step is narrowed to
    bf16, the selected rows included."""
    from repro.fleet import stages
    product = _cell_local_sgd(one_chip)
    monkeypatch.setattr(stages, "select_rows", lambda x, idx: x[idx])
    gather = _cell_local_sgd(one_chip)
    assert re.search(r"= f32\[1000,128,784\]\S* convolution\(.*"
                     r"operand_precision=\{highest,highest\}", product)
    assert all(k[2] == ("f32",) for k in _element_types(product, True))
    a, b = _element_types(product), _element_types(gather)
    assert {k: n for k, n in a.items() if k[0]} == \
        {k: n for k, n in b.items() if k[0]}
    # of the unnamed layout copies, only f32 ones may differ (the gather
    # path copies the shards into its own layout)
    assert sum(n for k, n in a.items() if "bf16" in k[2]) == \
        sum(n for k, n in b.items() if "bf16" in k[2])


_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*)")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _instructions(text):
    """(name, output shape text, opcode, rest of the line) of every
    instruction of a compiled module, fused bodies included."""
    return [m.groups() for m in map(_INSTR.match, text.splitlines()) if m]


def _f32_beside_bf16(text):
    """Fusions that output an array both as bf16 and as f32."""
    out = []
    for name, shape, op, _ in _instructions(text):
        arrays = _ARRAY.findall(shape)
        dims = lambda t: {d for e, d in arrays if e == t}
        if op == "fusion" and dims("bf16") & dims("f32"):
            out.append(name)
    return out


def _mask_reads(text):
    """The element types and shapes each u8 or u16 fusion reads."""
    shapes = {name: shape for name, shape, _, _ in _instructions(text)}
    reads = []
    for _, shape, op, rest in _instructions(text):
        if op == "fusion" and re.match(r"u(8|16)\[", shape):
            args = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
            reads += [_ARRAY.findall(shapes[a]) for a in args]
    return [a for r in reads for a in r]


def _convolutions(text):
    """Each convolution as (output shape with layout, operand shapes with
    layouts, window, dim_labels, feature_group_count, operand_precision),
    counted."""
    shapes = {name: shape for name, shape, _, _ in _instructions(text)}
    out = collections.Counter()
    for _, shape, op, rest in _instructions(text):
        if op != "convolution":
            continue
        operands = tuple(shapes[a] for a in
                         re.findall(r"%([\w.\-]+)", rest.split(")")[0]))
        attrs = tuple(
            (m.group(1) if m else None) for m in
            (re.search(rf"\b{k}=(\{{[^}}]*\}}|[^ ,]+)", rest)
             for k in ("window", "dim_labels", "feature_group_count",
                       "operand_precision")))
        out[(shape, operands) + attrs] += 1
    return out


def test_local_sgd_relu_keeps_no_f32_pre_activation_for_v5e(one_chip,
                                                           monkeypatch):
    """At the 1,000-node cell's shape, the CNN's ReLU takes its backward
    mask from the bf16 activation the next layer reads: no fusion writes
    an f32 copy of a convolution's activation beside the bf16 one, and no
    mask fusion reads conv2's f32 pre-activation.  Every convolution is
    the one `jax.nn.relu`'s program has, which does both."""
    from repro.models import cnn
    conv2 = ("f32", "1000,128,7,7,32")
    new = _cell_local_sgd(one_chip)
    monkeypatch.setattr(cnn, "relu", jax.nn.relu)
    old = _cell_local_sgd(one_chip)
    assert _f32_beside_bf16(new) == []
    assert conv2 not in _mask_reads(new)
    assert ("bf16", "1000,128,7,7,32") in _mask_reads(new)
    assert len(_f32_beside_bf16(old)) == 2
    assert conv2 in _mask_reads(old)
    convs = _convolutions(new)
    assert sum(convs.values()) >= 6
    assert convs == _convolutions(old)
