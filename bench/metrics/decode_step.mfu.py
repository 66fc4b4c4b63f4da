"""Roofline share of the whole decode round, in %: for each round the
larger of its model FLOPs over the bf16 peak and its bytes (weights once,
live KV pages) over the HBM peak, summed, over the device time of the
decode-round programs."""
from bench import flops, readers


def read(run):
    progs = readers.programs_named(run, "decode_round")
    rounds = readers.decode_rounds(run)
    if not progs or not rounds or len(progs) != len(rounds):
        return None
    m, pk, ps = run.model, run.peaks, run.info["page_size"]
    need = sum(max(flops.decode_round_flops(m, r) / pk["bf16_flops"],
                   flops.decode_round_bytes(m, r, ps) / pk["hbm_bytes_per_s"])
               for r in rounds)
    return 100.0 * need / sum(e.dur for e in progs)
