"""Share of the window's prompt tokens that were served from the prefix trie
and not computed: reused / (reused + computed), from the program's
``prefill_chunk`` spans — ``tokens`` of every chunk is what it computed, and
``hit`` of a request's first chunk (the one that starts where its hit ends)
what its admission found cached. A guard on the traffic: document
question-answering at 3-5 asks a document reads near 0.7; near 0 the asks
are not reaching the trie and the cell measures something else."""
NAME = "prefix_reuse_share"
UNIT = "ratio"
LAYER = "scheduler"
MOVES = "tpot_p50_ms"
CELLS = ["openpangu-*", "pangu-*"]


def read(run):
    from benchmark.reduce import cell
    chunks = cell.span_arguments(run, 'prefill_chunk')
    computed = sum(int(a.get('tokens', 0)) for a in chunks)
    reused = sum(int(a['hit']) for a in chunks
                 if 'hit' in a and int(a.get('start', -1)) == int(a['hit']))
    if not computed:
        return None
    return reused / (reused + computed)
