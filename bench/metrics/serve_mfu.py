"""Model FLOP utilisation of the whole serving step: forward FLOPs of the
true prompt tokens prefilled and of every decode token computed in the
window (counted from the configuration's shapes: its module's counts,
else harness/counts.py), over the window's seconds times the chips' bf16
peak."""


def read(rec):
    peak = rec["peaks"]["bf16_flops"] * rec["chips"] * rec["seconds"]
    return 100.0 * rec["flops"] / peak if rec["flops"] else None
