"""Public testing utilities (the reference's
`tests/python/common/check_utils.py` helpers, exposed as a library module
so users can gradient-check their own custom operators and symbols).

    import mxnet_tpu as mx
    sym = my_custom_op(data=mx.sym.Variable("data"))
    mx.test_utils.check_numeric_gradient(sym, {"data": x})
"""
from __future__ import annotations

import os

import numpy as np

from .base import MXNetError


def force_cpu_devices(n=8):
    """Force an ``n``-device virtual CPU platform for multi-device tests.

    The TPU build's version of the reference's hardware fakes (SURVEY §4:
    ctx_group on cpu(0)/cpu(1), localhost PS processes): mesh/SPMD logic runs
    on ``n`` virtual CPU devices.  Must be called BEFORE the first jax
    backend initialization.  Works whether or not jax is already imported
    (``jax.config.update`` takes effect until the backend is initialized),
    and rewrites a preexisting ``--xla_force_host_platform_device_count``
    flag if it asks for fewer than ``n`` devices.  For tests and dry runs
    only: nothing that is meant to run on the chip calls this.
    """
    import os
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + " --xla_force_host_platform_device_count=%d"
                 % n).strip()
    elif int(m.group(1)) < n:
        flags = (flags[:m.start()]
                 + "--xla_force_host_platform_device_count=%d" % n
                 + flags[m.end():])
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses

    import jax

    jax.config.update("jax_platforms", "cpu")
    if len(jax.devices()) < n:
        raise MXNetError(
            "force_cpu_devices(%d): jax backend already initialized with "
            "%d devices; call before any jax computation (fresh process)"
            % (n, len(jax.devices())))


def reldiff(a, b):
    """Normalized L1 difference (`check_utils.py` reldiff)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    diff = np.sum(np.abs(a - b))
    norm = np.sum(np.abs(a)) + np.sum(np.abs(b))
    if norm == 0:
        return 0.0
    return diff / norm


def numeric_grad(f, x, eps=1e-4):
    """Central-difference gradient of scalar ``f`` at numpy array ``x``."""
    x = np.asarray(x, np.float64)
    grad = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + eps
        fp = f(x.astype(np.float32))
        x[idx] = orig - eps
        fm = f(x.astype(np.float32))
        x[idx] = orig
        grad[idx] = (fp - fm) / (2 * eps)
        it.iternext()
    return grad


def check_numeric_gradient(sym, location, grad_nodes=None, rtol=1e-2,
                           atol=None, aux_states=None, eps=1e-4):
    """Assert executor backward() matches finite differences.

    sym : Symbol whose summed outputs form the loss (head-grad of ones,
        matching Executor.backward's default).
    location : dict arg_name -> numpy array (every argument).
    grad_nodes : names to check (default: every floating arg in location).

    Both the analytic backward and the finite-difference probes run with
    ``is_train=True`` so train/eval-divergent operators (BatchNorm batch
    statistics) are differentiated and probed as the SAME function.
    """
    from . import cpu, nd

    arg_names = sym.list_arguments()
    for n in location:
        if n not in arg_names:
            raise MXNetError("check_numeric_gradient: %r not an argument "
                             "(args: %s)" % (n, arg_names))
    ctx = cpu()
    args = {n: nd.array(np.asarray(location[n], np.float32))
            for n in arg_names}
    grads = {n: nd.zeros(np.asarray(location[n]).shape) for n in arg_names}
    aux_list = None
    if aux_states:
        aux_list = [nd.array(aux_states[n])
                    for n in sym.list_auxiliary_states()]
    exe = sym.bind(ctx, args, grads, "write", aux_list)
    exe.forward(is_train=True)
    exe.backward()
    grad_nodes = grad_nodes or [
        n for n in location
        if np.issubdtype(np.asarray(location[n]).dtype, np.floating)]
    analytic = {n: grads[n].asnumpy() for n in grad_nodes}

    # ONE probe executor reused for every finite-difference eval: updating
    # a bound arg and re-running forward hits the XLA compile cache
    probe = sym.bind(ctx,
                     {n: nd.array(np.asarray(location[n], np.float32))
                      for n in arg_names},
                     None, "null", aux_list)
    for name in grad_nodes:
        def f(x, _name=name):
            probe.arg_dict[_name][:] = x
            outs = probe.forward(is_train=True)
            return float(sum(o.asnumpy().astype(np.float64).sum()
                             for o in outs))

        expected = numeric_grad(f, np.asarray(location[name]).copy(),
                                eps=eps)
        probe.arg_dict[name][:] = np.asarray(location[name], np.float32)
        got = analytic[name]
        rd = reldiff(got, expected)
        if rd > rtol and (atol is None
                          or np.abs(got - expected).max() > atol):
            raise AssertionError(
                "numeric gradient check failed for %r: reldiff %.3g > %.3g"
                "\nanalytic=%s\nnumeric=%s"
                % (name, rd, rtol, got, expected))
    return exe


class FullForward:
    """The serving tests' reference: the Symbol's full forward of
    `get_transformer_lm` bound to a `TransformerKVModel`'s parameters, no
    cache and no scheduler, every position recomputed at every call."""

    def __init__(self, model, params):
        from . import cpu, nd
        from .models.transformer import get_transformer_lm

        self.vocab_size, self.seq_len = model.vocab_size, model.seq_len
        net = get_transformer_lm(
            model.vocab_size, model.seq_len, num_layers=model.num_layers,
            num_heads=model.num_heads, num_embed=model.num_embed,
            num_ffn_hidden=model.num_ffn_hidden, use_bias=model.use_bias)
        sym = net.get_internals()["pred_output"]
        args = {n: nd.array(params[n]) for n in model.param_shapes()}
        args["data"] = nd.zeros((1, model.seq_len))
        self._exe = sym.bind(cpu(), args, grad_req="null")

    def logits(self, tokens):
        """(len(tokens), vocab) logits of one sequence; the mask is causal,
        so the padding behind it is inert."""
        n = len(tokens)
        data = np.zeros((1, self.seq_len), np.float32)
        data[0, :n] = tokens
        out = self._exe.forward(is_train=False, data=data)[0].asnumpy()
        return out.reshape(self.seq_len, self.vocab_size)[:n]

    def greedy(self, prompt, max_new, min_gap=1e-3):
        """Greedy tokens of ``prompt``, the whole sequence recomputed for
        every token, as far as the context reaches.  A step whose top two
        logits lie within ``min_gap`` raises: there an engine's rounding
        could pick the other token, so the test's seed is at fault."""
        seq, out = [int(t) for t in prompt], []
        while len(out) < max_new and len(seq) <= self.seq_len:
            last = self.logits(seq)[-1]
            second, first = np.sort(last)[-2:]
            if first - second <= min_gap:
                raise AssertionError(
                    "FullForward.greedy: near-tie (%.3g) at position %d of "
                    "prompt %s: pick another seed"
                    % (first - second, len(seq), seq[:len(prompt)]))
            out.append(int(np.argmax(last)))
            seq.append(out[-1])
        return out


def aot_v5e_mesh():
    """One-device Mesh over a DESCRIBED (not attached) v5e topology: the
    target for compiling a program for the chip with no chip present
    (ADR-11; `scripts/ce_roofline.py`, `SPMDTrainer(abstract=True)`).
    libtpu is loaded into this process and stays loaded — compile in this
    process, never in a child.  Raises MXNetError when the installed
    jaxlib/libtpu pair cannot describe the topology."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        raise MXNetError("no AOT TPU topology support: %s"
                         % str(e)[:200]) from e
    return Mesh(np.array(topo.devices[:1]), ("data",))
