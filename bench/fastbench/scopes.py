"""The program's named scopes on the device's ops in a profiler trace.

The program runs each kernel family's wrapper under
``jax.named_scope("ops.<family>")`` (``repro/kernels/ops.py``), each
Evoformer sub-module under ``evoformer.<name>``, and the embedding,
recycling, heads, structure module, loss and optimizer under
``alphafold.*``, ``structure.*`` and ``train.*``. XLA keeps the scope path
in each HLO instruction's ``op_name`` metadata, backward ops under
``transpose(jvp(...))``.

Where the path is found, as recorded on a v5e with JAX 0.9, in the order
tried: (1) xprof writes it as the ``tf_op`` stat (``"<op_name>:"``) of each
``XLA Ops`` event's metadata, but ``jax.profiler.ProfileData`` gives only the
event's own stats (device offset and duration); (2) the ``/host:metadata``
plane holds the module's ``Hlo Proto``, which needs XLA's protobuf classes
that JAX does not ship; (3) the compiled program's text
(``Compiled.as_text()``) holds it: each event is named by its instruction's
text (``%fusion.12 = ...``), and the instruction of that name in the text
carries ``metadata={op_name="..."}``. ``op_names`` reads that map from the
text, and ``load`` applies it to the trace.

An op's family is the last ``ops.<family>`` component of its path: a
family's own padding, reshapes, backward and remat recompute carry it on
every leg (Pallas, XLA or the jnp oracle), so its time is read the same
whichever implements it. Ops outside every family have none.
"""
from __future__ import annotations

import re

from fastbench import trace

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = [^\n]*?op_name="([^"]*)"', re.M)
_SCOPE = re.compile(r"(?:^|[/(])(?:ops|evoformer|alphafold|structure|train)"
                    r"\.\w+")
_FAMILY = re.compile(r"(?:^|[/(])ops\.(\w+)")
_SUBMODULE = re.compile(r"(?:^|[/(])((?:evoformer|alphafold|structure|train)"
                        r"\.\w+)")


def family(path: str):
    """``.../ops.attention/ops.attention/pad`` -> ``attention``; None
    outside every family."""
    found = _FAMILY.findall(path)
    return found[-1] if found else None


def submodule(path: str):
    """The innermost Evoformer sub-module or model part on the path
    (``evoformer.msa_row_attention``, ``alphafold.loss``, ...); None
    where there is none."""
    found = _SUBMODULE.findall(path)
    return found[-1] if found else None


def has_scope(path: str) -> bool:
    return bool(_SCOPE.search(path))


def op_names(hlo_text: str) -> dict:
    """{instruction name: its ``op_name``} of a compiled program's text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def load(path: str, op_names: dict, device_ids=None) -> dict:
    """{device: [(start, end, scope path)]} of the TensorCore ops of the
    trace at ``path``, each looked up by its instruction's name in
    ``op_names`` (``""`` where it carries none), for the devices in
    ``device_ids`` (all when None). Unclipped: ``clip`` cuts them to a
    window."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None
                     and int(m.group(1)) not in device_ids):
            continue
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                out[int(m.group(1))] = [
                    (e.start_ns, e.start_ns + e.duration_ns,
                     op_names.get(trace._OP.match(e.name).group(1), ""))
                    for e in line.events]
    return out


def clip(scopes: dict, window) -> dict:
    """The leaf ops (``trace.leaves``) of each device, clipped to
    ``window``: the same ops, cut the same way, as ``Trace.ops``."""
    lo, hi = window
    return {d: trace._clip(trace.leaves(evs), lo, hi)
            for d, evs in scopes.items()}


def seconds(scopes: dict, families) -> float:
    """Device seconds, as a mean over the devices, of the ops whose family
    is in ``families``; with ``families`` None, of the ops in no family."""
    want = None if families is None else set(families)
    total = sum(e - s for evs in scopes.values() for s, e, p in evs
                if (family(p) is None if want is None
                    else family(p) in want))
    return total * 1e-9 / len(scopes)
