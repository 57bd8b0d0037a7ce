"""Kernels (kernels/pallas_reduce.py, kernels/groupby.py): device self time
of the group-reduce operations per grouped query of the traced window. On
one chip the reduce is the Pallas kernel, which the trace prints as a custom
call to `tpu_custom_call`; under the mesh it is XLA's scatter, which the TPU
compiler emits as a fusion of kind kCustom (on one chip the same kind is the
small scatter that compacts the answer). The program names neither (no
jax.named_scope, no kernel name), so the match is by what the compiler
prints."""
import re

UNIT = "ms"
KERNEL_OP = re.compile(r"tpu_custom_call|fusion:kCustom|scatter")
GROUPED = re.compile(r"^q[234]\.")


def read(ctx):
    if ctx.trace is None:
        return None
    qs = [q for q in ctx.trace["queries"]
          if q["whole"] and GROUPED.match(q["template"])]
    if not qs:
        return None
    total = sum(sec for q in qs for name, sec in q["op_s"].items()
                if KERNEL_OP.search(name))
    return total * 1000.0 / len(qs)
