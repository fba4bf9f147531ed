"""The training step's profiler scopes (``jax.named_scope`` in the
program) as XLA keeps them, and the benchmark's resolver that maps each
compiled instruction to one scope (``bench/lib/scopes.py``).  A tiny
OLMo step with remat, compiled on the CPU."""

import collections
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.core.formats import QuantConfig
from repro.train.steps import TrainHParams, init_train_state, make_train_step

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import scopes  # noqa: E402

RECIPES = {"moss": QuantConfig(mode="moss", weight_scaling="auto"),
           "bf16": QuantConfig(mode="bf16")}
HP = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
B, S = 2, 128


@pytest.fixture(scope="module", params=sorted(RECIPES))
def step_hlo(request):
    cfg = get_config("olmo-7b", smoke=True).replace(
        quant=RECIPES[request.param], remat=True)
    state = jax.eval_shape(lambda k: init_train_state(cfg, HP, k),
                           jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    step = jax.jit(make_train_step(cfg, HP), donate_argnums=(0,))
    text = step.lower(state, {"tokens": tok, "labels": tok}).compile().as_text()
    return request.param, text


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def test_every_scope_in_the_compiled_step(step_hlo):
    recipe, text = step_hlo
    found = {scopes.resolve(n) for n in _op_names(text)}
    want = set(scopes.SCOPES) - ({"moss.scale"} if recipe == "bf16" else set())
    assert want <= found
    if recipe == "bf16":
        assert "moss.scale" not in found
    # backward and recomputed forward ops keep their scope
    names = _op_names(text)
    assert any("transpose(jvp(head))" in n for n in names)
    assert any("rematted_computation" in n and scopes.resolve(n) == "mlp"
               for n in names)


def _top_level(text):
    """(instruction, opcode) outside fused and reducer computations."""
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if m and comp not in called:
            out.append(m.groups())
    return out


def test_resolver_gives_the_step_ops_one_scope(step_hlo):
    _, text = step_hlo
    names = scopes.hlo_scopes(text)
    ops = [(n, op) for n, op in _top_level(text)
           if op in ("fusion", "custom-call", "dot", "reduce")]
    assert ops
    assert all(names[n] is None or names[n] in scopes.SCOPES for n, _ in ops)
    by = collections.Counter(names[n] is not None for n, _ in ops)
    assert by[True] >= 0.9 * len(ops), by


def _eqns(jaxpr, outer=""):
    """(name stack, eqn) of every equation, sub-jaxprs included."""
    for e in jaxpr.eqns:
        stack = outer + str(e.source_info.name_stack)
        yield stack, e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub, stack + "/")


def test_qmm_backward_ops_carry_a_scope_under_transpose():
    """The MOSS backward (``core.linear._qmm_bwd``) of a linear in the
    caller's ``head`` scope: the weight's transpose and the gradient's
    per-tensor amax and e5m2 cast are in ``moss.scale``, the rest in
    ``head``, all under the transpose."""
    from repro.core.linear import QT, qlinear

    x = jnp.ones((64, 128), jnp.bfloat16)
    w = jnp.full((128, 256), 0.01, jnp.float32)

    def f(w):
        with jax.named_scope("head"):
            y = qlinear(x, QT(w, jnp.float32(0.01 / 448)), RECIPES["moss"])
        return jnp.sum(y.astype(jnp.float32) ** 2)

    bwd = [(stack, e) for stack, e in
           _eqns(jax.make_jaxpr(jax.grad(f))(w).jaxpr)
           if stack.startswith("transpose(jvp(head")]
    assert {scopes.resolve(stack) for stack, _ in bwd} == {"head",
                                                           "moss.scale"}
    scaled = {(e.primitive.name, str(e.outvars[0].aval.dtype))
              for stack, e in bwd if scopes.resolve(stack) == "moss.scale"}
    assert {("transpose", "float8_e4m3fn"), ("reduce_max", "float32"),
            ("convert_element_type", "float8_e5m2")} <= scaled


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/transpose(jvp(head))/moss.scale/convert", "moss.scale"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attn/attn/attn_core/while/body/exp", "attn_core"),
    ("jit(train_step)/jvp(loss)/reduce_max", "loss"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/jit(clip)/min", None),
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_slice", None),
])
def test_resolve_takes_the_innermost_scope(op_name, scope):
    assert scopes.resolve(op_name) == scope
