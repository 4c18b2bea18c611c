"""Expert layer: 100 x the token-expert pairs of the run's newest step that fell on the experts this rank holds over all the step's pairs, the mean over the expert blocks, from the counts the program's expert layers return in the timed step itself (``jobs/gpt_latent_moe_hybrid_dp.py::Job.held_pairs_pct``; ``100 held / router`` under an even router, 1.5625 at 8 of 512: a router that has drifted towards the held experts reads more before the rate shows it). None where the job keeps no such counts."""


def read(ctx):
    held_pairs_pct = getattr(ctx.job, "held_pairs_pct", None)
    return held_pairs_pct() if held_pairs_pct else None
