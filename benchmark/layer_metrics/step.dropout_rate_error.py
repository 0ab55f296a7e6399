"""Residual dropout's realised rate against the rate the configuration
states, from the program's counters: ``|1 - dropout_kept / dropout_total -
DCT_DROPOUT|``, the two element counts summed over every site, step and
epoch read. It shows that a faster step still draws every mask at the
published rate: a mask shared between sites or steps, a coarser threshold
or a site left out moves it far past a run's sampling error (1e-5 and
less over an epoch's 2e10 elements).

The counters come with the trainer's ``epoch_end`` events. The stamp that
closes the window ends ``fit`` before the last epoch's event is written, so
the events cover the warm-up epoch and every epoch of the window but the
last. The warm-up epoch is left out as set-up wherever another remains; a
window of ONE epoch leaves only the warm-up epoch's event, which is read
then: the same compiled program on the same shapes. ``None`` where no
epoch counted a mask (dropout 0, or a program without the counters)."""

LAYER = "step"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "fit_tokens_per_s"


def read(art):
    counted = [
        e for e in art["events"]
        if e.get("event") == "epoch_end" and e.get("dropout_total")]
    counted = counted[1:] or counted
    if not counted:
        return None
    kept = sum(e["dropout_kept"] for e in counted)
    total = sum(e["dropout_total"] for e in counted)
    rate = float(art["config"]["program"]["env"]["DCT_DROPOUT"])
    return abs(1.0 - kept / total - rate)
