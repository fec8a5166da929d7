"""Model FLOP/s utilisation of training: the FLOPs a step requires
(``work.train_step``: 6 per parameter of every product per token, plus
the H1D operator forward and backward) times the steps completed, over
the window and the chips' bf16 peak, in %."""


def read(r):
    w = r["window"]
    if not w.get("steps"):
        return None
    mix = r["mix"]
    flops = r["work"].train_step(r["cfg"], mix["batch"], mix["seq_len"])
    rate = flops["flops"] * w["steps"] / w["seconds"]
    return 100.0 * rate / (r["peaks"]["bf16_flops_per_s"] * r["chips"])
