"""Driver `serve_closed_spec`: `serve_closed_window`'s run, unchanged
(loaded, not copied: `serve_closed`'s loop, the comparison of the emitted
tokens with the reference's logits, the choice of the requests that are
held to it, the counters read at the window's ends), for a model that
DRAFTS with a module of its own and verifies the draft in the next
iteration (the EXAONE-MoE family's multi-token-prediction module). It
adds:

* the DRAFTS. A wrong MTP module changes no emitted token (a draft that
  is not the main model's own token is thrown away), so the engine hands
  back each iteration's standing draft with its read (`Request.drafts`:
  (k, token), the module's guess at `generated[k]`) and this kind holds
  them to the reference's `draft_logits_at` (the module's logits from the
  reference's own hidden states) by the same gap and the same near-tie
  rule as the tokens: the drafts of a request FROM the first position
  whose routing margin, in a decoder layer or in the module's block, is
  under `tolerance.margin_epsilon` are left out and counted. The requests
  are chosen by `serve_closed_window`'s rule (`_window_first`), up to
  `check_draft_requests`, and a run that checks fewer drafts than tokens
  is not correct;
* the module's counters over the window, read at its two ends with the
  others: `mtp` (drafts verified, drafts accepted) and `mtp_moe` (its
  block's experts);
* the work arithmetic with a lane's K/V counted ONCE an iteration: the
  program's `window_rows` counts every query row's read (two a lane), so
  the ring bytes take half of it; to `work["decode_bytes"]` the module's
  routed experts that met a row; `work["mtp"]`, the module's least bytes
  (its weights once, its live K/V once a lane), for `mtp_draft_roofline`.
"""
from __future__ import annotations

import functools
import os
import time


def _draft_gaps(family, params, sizes, completed, cell, seed,
                longest_output: int, counted: dict):
    """`serve_closed._logit_gaps` for the drafts: for a seeded sample of
    the requests given, at each draft read, how far the draft sits below
    the best of the reference's MTP logits at its position (0 = it is the
    argmax). `counted` takes the drafts checked and those left out for a
    routing margin within rounding."""
    import jax
    import numpy as np
    epsilon = float(cell["tolerance"]["margin_epsilon"])
    limit = int(cell["reference_max_tokens"])
    fits = [r for r in completed
            if len(r.prompt) + len(r.generated) <= limit]
    rng = np.random.default_rng(seed)
    picks = [fits[i] for i in rng.permutation(len(fits))
             [:int(cell["check_requests"])]]
    spec = family.reference_spec_of
    fn = jax.jit(lambda p, ids, pos: family.reference.draft_logits_at(
        p, ids, pos, spec))
    gaps = []
    for r in picks:
        seq = r.prompt + r.generated
        padded = 1 << max(7, (len(seq) - 1).bit_length())
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        # the draft of generated[k] was made at position L + k - 2, from
        # the hidden state there and the token after it
        drafts = dict(r.drafts)
        ks = sorted(k for k in drafts if 1 <= k <= len(r.generated))
        if not ks:
            continue
        at = np.asarray(ks) + len(r.prompt) - 2
        pos = np.full((longest_output + 1,), at[0], np.int32)
        pos[:len(at)] = at
        logits, least = (np.asarray(x)[:len(at)]
                         for x in fn(params, ids, pos))
        keep = least >= epsilon
        counted["checked"] += len(at)
        counted["left_out"] += int((~keep).sum())
        gaps.extend(float(logits[i].max() - logits[i][drafts[k]])
                    for i, k in enumerate(ks) if keep[i])
    return gaps, len(picks)


def run(ctx: dict) -> dict:
    from benchmark import harness
    config, cell = ctx["config"], ctx["cell"]
    window_kind = harness.load_module(ctx["root"], "kinds",
                                      "serve_closed_window")
    moe_kind = harness.load_module(ctx["root"], "kinds", "serve_closed_moe")
    family = moe_kind._Family(ctx["family"], ctx["family"].reference)
    family.reference_spec_of = ctx["family"].reference_spec(config)
    sizes = family.sizes(config)

    class AtTheWindowsEnds(moe_kind._AtTheWindowsEnds):
        """Each reading takes ALL the model's counters off the device."""

        @property
        def count(self):
            from paddle_tpu.inference import serving
            for eng in serving.live_engines():
                if eng.model is self._family.model:
                    self.readings.append(eng.device_counters())
            return self._compiles.count

    ends = AtTheWindowsEnds(ctx["compiles"], family)
    with window_kind._submissions(family) as submitted:
        run = window_kind.run({**ctx, "family": family, "compiles": ends})
    run["kind"] = os.path.splitext(os.path.basename(__file__))[0]
    tolerance = cell["tolerance"]

    # ---- the drafts against the reference's MTP module
    t = time.monotonic()
    params = {k: p.data for k, p in family.model.named_parameters()}
    counted = {"checked": 0, "left_out": 0}
    gaps, checked = window_kind._window_first(
        functools.partial(_draft_gaps, counted=counted), submitted,
        ctx["traffic"], sizes["window"], family, params, sizes, None,
        {**cell, "check_requests": int(cell["check_draft_requests"])},
        ctx["seed"], int(ctx["traffic"]["output_tokens"]["max"]))
    report = run["report"]
    report["setup_parts_s"]["draft_reference_s"] = time.monotonic() - t
    tokens_checked = report["left_out_positions"][1]
    if not gaps:
        run["notes"].append("no draft was compared with the reference")
    elif max(gaps) > float(tolerance["logit_gap"]):
        run["notes"].append(
            f"a draft sits {max(gaps):.4f} below the best logit of the "
            f"reference's MTP module (tolerance {tolerance['logit_gap']})")
    if counted["checked"] < tokens_checked:
        run["notes"].append(
            f"{counted['checked']} drafts were checked against "
            f"{tokens_checked} tokens: the drafts have to be held to the "
            f"reference over at least as many positions")
    left_out = counted["left_out"] / max(1, counted["checked"])
    if left_out > float(tolerance["left_out_share_max"]):
        run["notes"].append(
            f"{counted['left_out']} of {counted['checked']} checked drafts "
            f"were left out for a routing margin under "
            f"{tolerance['margin_epsilon']} (at most "
            f"{float(tolerance['left_out_share_max']):.0%} may be)")
    report.update(max_draft_gap_vs_reference=max(gaps) if gaps else None,
                  checked_draft_requests=checked,
                  left_out_drafts=[counted["left_out"], counted["checked"]])

    # ---- the module's counters over the window
    if len(ends.readings) == 2:
        first, last = ends.readings
        drafted, accepted = (int(x) for x in last["mtp"] - first["mtp"])
        touched = int((last["mtp_moe"] - first["mtp_moe"])[1])
    else:       # `serve_closed_window` has said so in the notes
        drafted = accepted = touched = 0
    run["counters"].update(mtp_drafted=drafted, mtp_accepted=accepted,
                           mtp_experts_touched=touched)

    # ---- a lane's K/V once an iteration, and the module's own work
    itemsize = family.model.wte.weight.data.dtype.itemsize
    work = run["work"]
    rows = work["window"]["rows"] / 2.0     # two query rows a lane
    work["decode_bytes"][-1] -= work["window"]["row_bytes"] * rows
    work["window"]["rows"] = rows
    mtp = family.mtp_bytes(config, itemsize)
    work["decode_bytes"].append(mtp["expert"] * touched)
    live = (report["live_kv_tokens_mean"] or 0.0) \
        * run["counters"]["iterations"]
    work["mtp"] = {"bytes": (mtp["fixed"] * run["counters"]["iterations"]
                             + mtp["expert"] * touched
                             + mtp["kv_token"] * live)}
    report["counted"].update(mtp_drafted=drafted, mtp_accepted=accepted,
                             mtp_experts_touched=touched,
                             window_rows_a_query=rows)
    return run
