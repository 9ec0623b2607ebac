"""Prefill's share of the chip's peak (%): the operations the model needs
for every prompt token prefilled in the window (bench/flops.py: padding
and capacity excluded, logits at each prompt's last token only), over the
summed time of the prefill calls, over the peak (bench/peaks.json)."""


def read(obs):
    t = sum(obs.get("prefill_s") or [])
    if obs.get("kind") != "serve" or t <= 0:
        return None
    return 100.0 * obs["prefill_flops"] / t / obs["peak_flops"]
