"""``opt_embed_roofline`` (``model`` layer, %): the least time the chip
could take for one Adam step over the embedding tables under ``--dtype
mixed`` — per parameter read w, g, m, v, write w, m, v and the bfloat16
compute copy, 30 bytes (``_relations.embedding_adam_bytes``), times the
``embedding_rows`` of the program's ``plan`` line and the input width,
over the HBM peak of ``peaks.json`` — over ``opt_embed_ms``.  The
update is elementwise: HBM bounds it.  A program without the scope or
the counter gives nothing to read."""


def read(run):
    rel = run.cell.module("layer_metrics", "_relations")
    ms = rel.named_scope_ms(run, "roc.opt.embed")
    _, resolved = rel.relation_layers(run)
    rows = resolved.get("embedding_rows")
    if not ms or not rows or run.peaks is None:
        return None
    width = resolved["embedding_bytes"] // (4 * rows)
    least_ms = (rel.embedding_adam_bytes(rows, width)
                / run.peaks["hbm_bytes_per_s"] * 1e3)
    return 100.0 * least_ms / ms
