"""Every Pallas entry point compiles for a TPU v5e — checked without a chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu", ...)`` gives a
compile-only client: ``jit(...).lower(abstract args on its devices).compile()``
runs the real XLA:TPU and Mosaic compilers.  Nothing here *runs* a kernel
(``chip_smoke.py`` does, on the chip); this keeps "the kernels compile" true
between chip runs.  The cases compile in a child process, because a Mosaic
check failure aborts the process instead of raising — the flash kernel's
padded-batch variant did exactly that before its validity operand was laid
out ``[B, 1, S]``.  It is ONE child running the cases in order (libtpu holds a
process-wide lockfile even for the compile-only client, so children cannot
overlap, and twelve interpreter starts would cost a minute): it reports after
each case, so an abort is charged to the case that caused it and the cases
behind it read "not reached".
"""

import os
import subprocess
import sys

import pytest

TOPOLOGY = "v5e:2x2"

_CHILD = r"""
import sys, traceback
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name=sys.argv[1])
except Exception as e:  # no compile-only TPU client in this install: the test skips
    print("NO_TOPOLOGY", type(e).__name__, str(e)[:300].replace("\n", " "), flush=True)
    sys.exit(0)
from accelerate_tpu.ops.pallas_attention import (
    pallas_attention, pallas_paged_attention, pallas_paged_window_attention,
)

sh = SingleDeviceSharding(topo.devices[0])
sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
loss = lambda f: (lambda *a: f(*a).astype(jnp.float32).sum())


def program(case, hd, b):
    s, h, kh = 2048, 8, 2
    q, kv = sds((b, s, h, hd)), sds((b, s, kh, hd))
    if case == "flash_fwd":
        return (lambda q, k, v: pallas_attention(q, k, v, block_size=512, interpret=False)), (q, kv, kv)
    if case == "flash_grad":
        f = loss(lambda q, k, v: pallas_attention(q, k, v, block_size=512, interpret=False))
        return jax.grad(f, argnums=(0, 1, 2)), (q, kv, kv)
    if case == "flash_kv_valid":
        f = loss(lambda q, k, v, m: pallas_attention(
            q, k, v, block_size=512, interpret=False, kv_valid=m))
        return jax.grad(f, argnums=(0, 1, 2)), (q, kv, kv, sds((b, s), jnp.bool_))
    slots, width, block, blocks, window = b, 8, 16, 64, 4
    pool, tables, lengths = sds((blocks, block, kh, hd)), sds((slots, width), jnp.int32), sds((slots,), jnp.int32)
    if case == "paged":
        return (lambda *a: pallas_paged_attention(*a, interpret=False)), (
            sds((slots, h, hd)), sds((slots, kh, hd)), sds((slots, kh, hd)), pool, pool, tables, lengths)
    return (lambda *a: pallas_paged_window_attention(*a, interpret=False)), (
        sds((slots, window, h, hd)), sds((slots, window, kh, hd)), sds((slots, window, kh, hd)),
        pool, pool, tables, lengths)


for spec in sys.argv[2:]:
    case, hd, b = spec.split(":")
    print("BEGIN", spec, flush=True)   # an abort after this line belongs to this case
    try:
        f, args = program(case, int(hd), int(b))
        text = jax.jit(f).lower(*args).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("compiled, but the executable holds no Mosaic kernel")
    except Exception:
        print("REFUSED", spec, traceback.format_exc()[-1500:].replace("\n", " | "), flush=True)
    else:
        print("COMPILED", spec, flush=True)
"""

CASES = [
    (case, hd, b)
    for hd in (64, 128)
    for case, b in (
        ("flash_fwd", 2),
        ("flash_grad", 2),
        ("flash_kv_valid", 1),
        ("flash_kv_valid", 8),
        ("paged", 4),
        ("paged_window", 4),
    )
]


# ``python -c`` puts its working directory first on sys.path: the child
# imports the accelerate_tpu of the checkout this file belongs to.
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCELERATE_TPU_COMPILE_CACHE="")
    env.pop("XLA_FLAGS", None)  # the child needs no virtual CPU devices
    return env


@pytest.fixture(scope="module")
def compiled():
    """{case: (verdict, detail)} from the one child."""
    specs = [f"{c}:{h}:{b}" for c, h, b in CASES]
    child = subprocess.run(
        [sys.executable, "-c", _CHILD, TOPOLOGY, *specs],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    if child.stdout.startswith("NO_TOPOLOGY"):
        pytest.skip(f"no compile-only TPU client for {TOPOLOGY}: {child.stdout.strip()}")
    results, began = {}, None
    for line in child.stdout.splitlines():
        word, _, rest = line.partition(" ")
        if word == "BEGIN":
            began = rest
        elif word in ("COMPILED", "REFUSED"):
            spec, _, detail = rest.partition(" ")
            results[spec] = (word, detail)
            began = None
    for spec in specs:
        if spec not in results:
            results[spec] = (
                ("ABORTED", f"the compiler process died (exit {child.returncode}) in this "
                            f"case:\n{child.stderr[-2000:]}")
                if spec == began
                else ("NOT REACHED", f"the compiler process died (exit {child.returncode}) in {began}")
            )
    return results


@pytest.mark.parametrize("case,hd,b", CASES, ids=[f"{c}-hd{h}-b{b}" for c, h, b in CASES])
def test_pallas_entry_point_compiles_for_v5e(compiled, case, hd, b):
    verdict, detail = compiled[f"{case}:{hd}:{b}"]
    assert verdict == "COMPILED", f"{case} head={hd} batch={b}: {verdict}\n{detail}"
