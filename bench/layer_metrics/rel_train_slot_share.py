"""``rel_train_slot_share`` (``aggregation`` layer, %): of the table
slots the relation scans of a typed model's *eval* program gather in
one forward and one backward pass, the share its *loss* program
gathers — 100 x the sum over the relational layers of
(``train_slots_fwd`` + ``train_slots_bwd``) over the sum of
(``slots_fwd`` + ``slots_bwd``), all four read off the ``rel_layers``
of the program's ``plan`` line.  A slot gathered for a row the loss
never reads costs what every slot costs, so this is the share of the
scan the train step still pays for; 100 means every layer sums every
relation for every row.  A layer without the ``train_`` keys (a parent
commit) counts its own slots: 100.  A count made by the program on the
host, so a rehearsal reads it too.  A program that resolves no
relation, or whose layout counts no slots for a pass (the ``segment``
edge-list reference has no backward table), gives nothing to read."""


def read(run):
    layers, _ = run.cell.module("layer_metrics",
                                "_relations").relation_layers(run)
    if not layers:
        return None
    whole = [l.get(k) for l in layers for k in ("slots_fwd", "slots_bwd")]
    train = [l.get("train_" + k, l.get(k)) for l in layers
             for k in ("slots_fwd", "slots_bwd")]
    if not all(whole) or not all(train):
        return None
    return 100.0 * sum(train) / sum(whole)
