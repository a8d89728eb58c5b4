"""jit'd public wrappers for the Pallas kernels, with XLA fallbacks and
recompute-from-oracle backward passes.

Off a TPU the kernels run under ``interpret=True`` (the kernel body
executes in Python), which checks results only.  On a TPU the same
``pl.pallas_call`` lowers to Mosaic; ``tests/test_tpu_compile.py`` compiles
each kernel for a described v5e.  ``custom_vjp`` backward recomputes
through the ref oracle (forward-optimized; there are no backward
kernels)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .backend import on_tpu as _on_tpu  # noqa: F401 - re-exported; the
# kernels' own interpret=None defaults resolve through kernels.backend, so
# the explicit interpret= threading below is belt-and-braces documentation
# of the contract: Mosaic on TPU, Python interpreter elsewhere.
from .flash_attention import flash_attention as _flash_fwd
from .router_topk import router_topk as _router_fwd
from .ssd_scan import ssd_scan as _ssd_fwd


# -- flash attention -----------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, window=0, block=128):
    return _flash_fwd(q, k, v, causal=causal, window=window,
                      block_q=block, block_k=block,
                      interpret=not _on_tpu())


def _fa_fwd(q, k, v, causal, window, block):
    return flash_attention(q, k, v, causal, window, block), (q, k, v)


def _fa_bwd(causal, window, block, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=causal, window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# -- chunked linear recurrence ----------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ssd_scan(q, k, v, log_a, chunk=128):
    return _ssd_fwd(q, k, v, log_a, chunk=chunk, interpret=not _on_tpu())


def _ssd_fwd_rule(q, k, v, log_a, chunk):
    return ssd_scan(q, k, v, log_a, chunk), (q, k, v, log_a)


def _ssd_bwd_rule(chunk, res, g):
    q, k, v, log_a = res
    _, vjp = jax.vjp(ref.ssd_scan_ref, q, k, v, log_a)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd_rule, _ssd_bwd_rule)


# -- router (routing itself carries no gradient; weights do, upstream) ------------
def router_topk(logits, top_k: int, capacity: int, block_t: int = 256):
    return _router_fwd(jax.lax.stop_gradient(logits), top_k, capacity,
                       block_t=block_t, interpret=not _on_tpu())
