"""Share of its roofline that the paged decode attention kernel
(``paged_decode_attention``, read in place through the block table)
reaches in the traced window, in %: the least time of the work of the
decode steps that ran inside the traced window (``bench/work.py``
``paged_attention_work``, from each step's live context) over the device
time of the operations in the kernel's scope (``jit(paged_decode_attention)``
in each instruction's ``op_name``, read from the decode entries' compiled
HLO) in the decode entries' programs (``jit_decode_bs<b>``), clipped to
the same window. Nothing to read, as in a model whose layers do not run
the kernel, gives nothing."""
from bench import work

SCOPE = "jit(paged_decode_attention)"
MODULES = "jit_decode_bs"


def read(rec):
    tr, names = rec.get("trace"), rec.get("op_names")
    steps = rec.get("traced_decodes")
    if tr is None or not names or not steps:
        return None
    kernel_s = 0.0
    for module, ops in tr.module_ops.items():
        if module.startswith(MODULES) and module in names:
            scope = names[module]
            kernel_s += sum(s for op, s in ops.items()
                            if SCOPE in scope.get(op, "").split("/"))
    if kernel_s <= 0:
        return None
    ops, nbytes = work.paged_attention_work(
        rec["model"], [(d[2], d[4]) for d in steps], rec["kv_itemsize"])
    return 100.0 * work.least_time_s(ops, nbytes, rec["peaks"]) / kernel_s
