"""Share of its roofline that the Pallas normalize reaches, per chip: the
least time its bytes allow (uint8 in, bfloat16 out, through HBM) over the
device time of the ops that carry its bytes from the caller's uint8
``(rows, H, W, 3)`` image to the caller's bfloat16 one.  Memory-bound.

The kernel is the train step's Mosaic call (``tpu_custom_call``) with
the result ``bf16[rows per chip, H*W*C]``: found by what it computes, as
its name changes with the mesh (``_normalize_impl.N`` on one chip,
``shard_map.N`` on four).

XLA places the kernel's operand and result in on-chip memory (``S(1)``
in the compiled HLO), so the kernel alone touches no HBM and reads
above 100% against it.  What carries its bytes: back from the kernel,
the data movement that feeds it (the relayouts and reshape to its flat
``(rows, H*W*C)`` operand, the copies of its scale and bias); forward,
the data movement that takes its flat result out to HBM and back into
the ``(rows, H, W, 3)`` image, and no further: what the model then does
with that image is the model's."""

from benchmarks import roofline
from benchmarks.metrics.common import roofline_pct

MOVES = {"copy", "reshape", "bitcast", "transpose", "copy-start", "copy-done"}


def read(rec):
    trace = rec.get("trace")
    if not trace:
        return None
    prefix = rec["programs"]["step"] + "/"
    ops = {k[len(prefix):]: o for k, o in trace["ops"].items()
           if k.startswith(prefix)}
    s = rec["shapes"]
    rows = s["rows"] // s["chips"]
    result = f"bf16[{rows},{s['normalize_row_elems']}]"
    image = f"bf16[{rows},{s['height']},{s['width']},3]"
    kernels = [k for k, o in ops.items() if o.get("target") ==
               "tpu_custom_call" and o.get("result") == result]
    calls = sum(ops[k]["count"] for k in kernels)
    if not calls:
        return None
    users = {}
    for k, o in ops.items():
        for src in o["operands"]:
            users.setdefault(src, []).append(k)
    chain = set(kernels)
    todo = list(kernels)
    while todo:
        for n in ops[todo.pop()]["operands"]:
            if n in ops and n not in chain and ops[n]["opcode"] in MOVES:
                chain.add(n)
                todo.append(n)
    todo = list(kernels)
    while todo:
        for n in users.get(todo.pop(), []):
            if n not in chain and ops[n]["opcode"] in MOVES:
                chain.add(n)
                if ops[n]["result"] != image:
                    todo.append(n)
    seconds = sum(ops[k]["seconds"] for k in chain) / calls
    flops, nbytes = roofline.normalize(rows, s["normalize_row_elems"])
    return roofline_pct(rec, flops, nbytes, seconds)
