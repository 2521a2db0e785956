"""train_mfu (whole round on the chip): model FLOPs of the local training
done in the traced window over window x chips x the chip's bf16 peak (the
program trains float32 at the TPU's default matmul precision, whose
passes are bfloat16).  Forward plus backward, three forwards, for every
real sample of every local epoch of every client trained; the pad rows
of the cohort bucket, the masked pad samples of a short last batch and
evaluation are not counted.  Moves round_s."""
from fedbench import work


def read(ctx):
    clients = sum(ctx.trained)
    if not clients or not ctx.rounds:
        return None
    samples = clients * ctx.traffic["train_per_client"] * \
        ctx.config["local"]["epochs"]
    flops = work.train_flops(ctx.reference.forward_flops(ctx.config),
                             samples)
    return 100.0 * flops / (ctx.trace.window_s * ctx.chips
                            * ctx.peak["bf16_flops"])
