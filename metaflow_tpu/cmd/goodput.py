"""`tpuflow goodput` — the run's chip-second breakdown, reconciled.

Derives the goodput ledger (metaflow_tpu/goodput.py) from a run's
persisted telemetry, renders the wall-clock-reconciled per-category
breakdown, and names the dominant loss — the run-level generalization
of the INPUT-BOUND / PIPELINE-BOUND verdicts `tpuflow metrics` prints
per subsystem. `--json` dumps the full ledger (the same document
`goodput.save_ledger` persists); `--openmetrics` prints the run-scope
exporter's OpenMetrics text instead.
"""

import json

from .. import goodput as goodput_mod
from .. import telemetry

# categories always rendered in this order (category order, losses
# grouped after productive work)
_RENDER_ORDER = goodput_mod.CATEGORIES + (goodput_mod.UNATTRIBUTED,)

_LABELS = {
    goodput_mod.PRODUCTIVE_STEP: "productive step compute",
    goodput_mod.COMPILE: "XLA compile",
    goodput_mod.INPUT_STALL: "input stall",
    goodput_mod.TRANSFER_STALL: "MPMD transfer stall",
    goodput_mod.UPDATE: "optimizer update",
    goodput_mod.CHECKPOINT_BLOCKED: "checkpoint blocked",
    goodput_mod.RESTORE_REPLAY: "restore + replayed work",
    goodput_mod.CAPACITY_WAIT: "capacity wait (parked)",
    goodput_mod.SERVE_PREFILL: "serve prefill",
    goodput_mod.SERVE_DECODE: "serve decode",
    goodput_mod.SERVE_IDLE: "serve idle",
    goodput_mod.UNATTRIBUTED: "unattributed",
}


def _category_rows(ledger):
    cats = dict(ledger["categories"])
    cats[goodput_mod.UNATTRIBUTED] = ledger["unattributed_chip_s"]
    observed = ledger["observed_chip_s"] or 1.0
    rows = []
    for cat in _RENDER_ORDER:
        seconds = cats.get(cat, 0.0)
        if seconds <= 0:
            continue
        rows.append((cat, seconds, seconds / observed))
    return rows


def render_ledger(ledger, echo=print):
    run = ledger.get("run_id") or "?"
    echo("goodput %s  wall %.1fs  chip-time %.1fs over %d lane(s)"
         % (run, ledger["wall_clock_s"], ledger["observed_chip_s"],
            len(ledger["lanes"])))
    for cat, seconds, frac in _category_rows(ledger):
        bar = "#" * max(1, int(round(frac * 40))) if seconds else ""
        echo("  %-22s %9.1fs  %5.1f%%  %s"
             % (_LABELS.get(cat, cat), seconds, frac * 100, bar))
    echo("  reconciliation: %.1f%% attributed (tolerance %.0f%%) -> %s"
         % (ledger["coverage"] * 100, ledger["tolerance"] * 100,
            "OK" if ledger["reconciled"] else "UNRECONCILED"))
    echo("  goodput: %.1f%% of chip-time productive"
         % (ledger["goodput_frac"] * 100))
    if ledger.get("parked"):
        total = sum(p["delay_s"] * max(1, p["world"])
                    for p in ledger["parked"])
        echo("  parked: %d capacity wait(s), %.1f chip-second(s) withheld"
             % (len(ledger["parked"]), total))
    verdict = loss_verdict(ledger)
    if verdict:
        echo("  verdict: %s" % verdict)


def loss_verdict(ledger):
    """One-line dominant-loss verdict, or None for a loss-free run."""
    dominant = ledger.get("dominant_loss")
    if not dominant or ledger.get("dominant_loss_s", 0.0) <= 0:
        return None
    observed = ledger["observed_chip_s"] or 1.0
    frac = ledger["dominant_loss_s"] / observed
    return ("dominant loss is %s (%s): %.1fs, %.1f%% of chip-time"
            % (dominant, _LABELS.get(dominant, dominant),
               ledger["dominant_loss_s"], frac * 100))


def show_goodput(flow_datastore, run_id, as_json=False,
                 openmetrics=False, persist=False, echo=print):
    """CLI entry. Returns 0, or 1 when the run has no telemetry or the
    ledger fails to reconcile within tolerance (CI gates on this)."""
    records = telemetry.read_run_records(flow_datastore, run_id)
    if not records:
        echo("no telemetry records for run %s" % run_id)
        return 1
    ledger = goodput_mod.derive_ledger(records, run_id=run_id)
    if persist:
        path = goodput_mod.save_ledger(flow_datastore, run_id, ledger)
        if path and not (as_json or openmetrics):
            echo("ledger persisted to %s" % path)
    if openmetrics:
        echo(goodput_mod.render_openmetrics(
            goodput_mod.ledger_metric_families(ledger)), )
    elif as_json:
        echo(json.dumps(ledger, indent=2, sort_keys=True))
    else:
        render_ledger(ledger, echo)
    return 0 if ledger["reconciled"] else 1
