"""Host time a step spends on the device lookup tables: the program's stages `ingest.merge.tables` (client, key and client-hash table) and `ingest.rank_table`, each a look-up while no writer or key is new and a rebuild and upload of what changed when one is, per step (phases recorder; host stages)."""


def read(w):
    stages = [w.phases.get(name) for name in ("ingest.merge.tables", "ingest.rank_table")]
    steps = len(w.dispatch_spans)
    found = [st["execute_s"] for st in stages if st]
    return sum(found) / steps * 1e3 if found and steps else None
