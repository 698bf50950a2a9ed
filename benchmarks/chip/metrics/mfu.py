"""Model FLOPs that the traced window's work needed (prompt tokens
computed, decode slot-steps at their live context, as the block
family's work counts give them) over device-busy time times peak bf16
FLOP/s, in percent. The trace spans the whole window."""
import work


def read(rec):
    tr = rec["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    fam, m, b = rec["work"], rec["dims"], rec["booking"]
    done = [fam.decode_step(m, ls, rec["page_size"])
            for ls in b.steps_in(tr["t0"], tr["t1"])]
    done += [fam.prefill_panel(m, n, p)
             for n, p in b.panels_in(tr["t0"], tr["t1"])]
    flops = sum(work.flops(w) for w in done)
    if not flops:
        return None
    return 100.0 * flops / (tr["busy_s"] * rec["peaks"]["bf16_flops"])
