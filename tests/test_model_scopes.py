"""The forward names its parts on the device's operations.

``repro.models.forward`` puts each GEMM under one role scope (``q``,
``k``, ``v``, ``o``, ``gate``, ``up``, ``down``, ``head``) and the
attention core under ``attn/core``.  XLA keeps the path in each HLO
instruction's ``op_name`` metadata, which a TPU trace carries on each
operation; the benchmark's per-layer readers find a GEMM's kernel events
by it (``bench/harness/scopes.py``).  Checked here on the compiled
program, under the Pallas SFC kernel (interpreted) and XLA's dot.
"""
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.models import DotEngine, forward, init_model

ROLES = ("q", "k", "v", "o", "gate", "up", "down", "head")
LAYER_ROLES = ROLES[:-1]
# the two einsums of the attention core (models/attention._sdpa)
SDPA = ("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd")
INSTR = re.compile(r'^\s*(?:ROOT )?%?(\S+) = \S+ ([\w-]+)\(.*'
                   r'op_name="([^"]*)"')
ENGINES = {"morton": DotEngine(schedule="morton", interpret=True),
           "xla": DotEngine(schedule="xla")}


@pytest.fixture(scope="module", params=sorted(ENGINES))
def compiled(request):
    """[(instruction, opcode, op_name)] of the smoke qwen3-1.7B forward's
    compiled program, and the engine's name."""
    cfg = get_smoke_config("qwen3_1_7b")
    params = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    engine = ENGINES[request.param]

    def logits(p, t):
        return forward(p, cfg, {"tokens": t}, engine)[0]
    text = jax.jit(logits).lower(params, tokens).compile().as_text()
    instrs = [m.groups() for m in map(INSTR.match, text.splitlines()) if m]
    return request.param, instrs


def _roles(path):
    """The distinct roles on a path (an interpreted kernel's branches
    repeat the path they were traced under)."""
    return sorted({p for p in path.split("/") if p in ROLES})


def test_each_gemm_has_exactly_one_role(compiled):
    name, instrs = compiled
    dots = [path for _, op, path in instrs if op == "dot"]
    gemms = [p for p in dots if not any(e in p for e in SDPA)]
    # every GEMM of the program lies under exactly one role
    assert gemms and all(len(_roles(p)) == 1 for p in gemms), gemms
    by_role = {_roles(p)[0] for p in gemms}
    assert by_role == set(ROLES)
    for p in gemms:
        role = _roles(p)[0]
        assert ("/layers/" in p) == (role in LAYER_ROLES), p
        assert f"/{'mlp' if role in ('gate', 'up', 'down') else 'attn'}/" \
            in p or role == "head", p
    if name == "morton":
        # every op of the SFC kernel (interpreted) carries its GEMM's role
        kernel = [p for _, _, p in instrs if "jit(sfc_matmul_pallas)" in p]
        assert kernel and all(len(_roles(p)) == 1 for p in kernel)


def test_attention_sits_under_attn_core(compiled):
    _, instrs = compiled
    sdpa = [p for _, op, p in instrs if op == "dot"
            and any(e in p for e in SDPA)]
    assert sdpa
    assert all("/layers/" in p and "/attn/core/" in p and not _roles(p)
               for p in sdpa), sdpa
