METRIC = {
    "name": "prefill_body_chunk_pct",
    "unit": "%",
    "layer": "generation scheduler and slot cache",
    "source": "program_counter",
    "why": "Share of the window's prefill chunk launches made with the program that ends without logits (tdn_gen_prefill_body_chunks_total over tdn_gen_prefill_chunks_total): every chunk that ends no prompt, and a resume's re-prefill. 75 with four chunks a prompt; 0 says the skip is off and every chunk pays the cross-decoder and the head.",
    "moves": "out_tokens_per_s",
}


def read(run):
    chunks = run.counters.get("prefill_chunks_total")
    body = run.counters.get("prefill_body_chunks_total")
    if not chunks or body is None:
        return None
    return 100.0 * body / chunks
