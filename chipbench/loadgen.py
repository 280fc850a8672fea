"""The load generator: a child process that never opens the device.

Started by the serving job with ``JAX_PLATFORMS=cpu``; talks to the server
the way a user's client does, through ``ServingClient.generate_stream``
over HTTP.  Reads one JSON line (url, vocabulary, start time, its share
of the sessions), runs each session on a thread of its own from its start
time on, stamps every token on arrival with the machine-wide monotonic
clock, and on the line ``stop`` abandons what is in flight and prints one
JSON line of records.  Nothing of it runs in the process that serves.
"""
import json
import sys
import threading
import time


def run_session(client, vocab, t_go, session, stop, records, lock):
    from chipbench.generators.sessions import prompt_tokens
    due = t_go + session["start_s"]
    delay = due - time.monotonic()
    if delay > 0 and stop.wait(delay):
        return
    k = 0
    while not stop.is_set():
        if k >= len(session["requests"]) and not session["repeat"]:
            return
        plen, max_new, tseed = session["requests"][k % len(session["requests"])]
        rec = {"session": session["index"], "k": k, "due": due,
               "prompt_len": plen, "max_new": max_new, "token_seed": tseed,
               "stamps": [], "tokens": [], "done": False, "error": None}
        stream = None
        try:
            prompt = prompt_tokens(vocab, tseed, plen)
            rec["sent"] = time.monotonic()
            stream = client.generate_stream(prompt, max_new_tokens=max_new)
            while True:
                try:
                    tok = next(stream)
                except StopIteration as fin:
                    rec["done"] = True
                    rec["engine_ttft_ms"] = (fin.value or {}).get("ttft_ms")
                    break
                rec["stamps"].append(time.monotonic())
                rec["tokens"].append(tok)
                if stop.is_set():
                    break                   # in flight at the close: dropped
        except Exception as e:              # noqa: BLE001 - a failed request
            rec["error"] = repr(e)          # is a result, reported as such
        finally:
            if stream is not None:
                stream.close()
        with lock:
            records.append(rec)
        k += 1
        due = time.monotonic()              # closed loop: next one is due now


def main():
    from mxnet_tpu.serving import ServingClient
    print("ready", flush=True)
    job = json.loads(sys.stdin.readline())
    client = ServingClient(job["url"], timeout_s=job["timeout_s"])
    stop, lock, records = threading.Event(), threading.Lock(), []
    threads = [threading.Thread(
        target=run_session, daemon=True,
        args=(client, job["vocab"], job["t_go"], s, stop, records, lock))
        for s in job["sessions"]]
    for t in threads:
        t.start()
    sys.stdin.readline()                    # "stop", or the parent is gone
    stop.set()
    deadline = time.monotonic() + 10.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    unfinished = sum(t.is_alive() for t in threads)
    with lock:
        print(json.dumps({"records": records, "unfinished": unfinished}),
              flush=True)


if __name__ == "__main__":
    main()
