"""``host_build_s`` (``host_table_build`` layer, s): wall seconds of
``cli.main`` from its entry to the moment it hands over the built
trainer (dataset load, symmetry check, resolve, table build, upload),
less the seconds of any ``compile`` event stamped before that moment."""


def read(run):
    build = run.seconds.get("build_s")
    if build is None:
        return None
    events = run.program_events()
    first = next((e["mono"] for e in events if e.get("cat") == "manifest"),
                 None)
    early = sum(e["lower_s"] + e["compile_s"] for e in events
                if e.get("cat") == "compile" and "compile_s" in e
                and first is not None and e["mono"] <= first)
    return build - early
