"""Disaggregated serving: a prefill pool and a decode pool exchanging
sessions through the KV transport (DESIGN.md §11.5).

The prefill-pool engine runs ``prefill_only``: every admitted request
prefills, samples its first token, and parks; ``export_session`` then
ships the lane + request state through the transport as one checksummed
blob. The decode-pool engine ``import_session``s each blob and decodes
it to completion. Token streams are bit-identical to one monolithic
engine — counter-based sampling keys and byte-exact lane round trips
make the continuation engine-independent.

Modes:

  (default)                  both pools in this process, loopback
                             transport, parity-checked against a
                             monolithic engine
  --tcp                      same, but the pools meet at a localhost
                             TCP blob peer (real sockets, same parity)
  --role decode --port P     THIS process hosts the blob peer on port P,
                             imports every session a prefill process
                             announces, decodes, and checks the token
                             streams against the manifest's expected
                             outputs (exit 0 iff bit-identical)
  --role prefill --connect HOST:PORT
                             THIS process computes the expected outputs
                             monolithically, then prefill-exports every
                             session to the peer plus a manifest blob

The two --role modes are the two-process harness CI runs: start the
decode process first, then the prefill process, and the decode process's
exit code is the bit-parity verdict.

Run:  PYTHONPATH=src python examples/disaggregate.py [--tcp]
"""
import argparse
import json
import sys
import time

import numpy as np

import jax

from repro.configs.base import ModelConfig, RoutingConfig
from repro.models.model import init_model
from repro.serve.engine import InferenceEngine, Request
from repro.serve.kvstore import KVStore, StoreConfig
from repro.serve.kvstore.remote import (LoopbackTransport, TCPStoreServer,
                                        TCPTransport)
from repro.launch.compile_cache import use_compile_cache

MANIFEST = "manifest"                   # blob announcing the shipped uids


def build_model(small: bool):
    cfg = ModelConfig(
        name="rt-disagg", family="dense",
        num_layers=2 if small else 4, d_model=128 if small else 256,
        num_heads=4 if small else 8, num_kv_heads=2 if small else 4,
        d_ff=256 if small else 512, vocab_size=1024,
        attention="local+routing",
        routing=RoutingConfig(num_clusters=8, local_window=32),
        dtype="float32")
    params, kstate = init_model(cfg, jax.random.PRNGKey(0))
    return cfg, params, kstate


def make_requests(cfg, n=6):
    rng = np.random.RandomState(1)
    prompt_lens = (16, 32, 48)
    return [Request(uid=uid,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=prompt_lens[uid % 3]).tolist(),
                    max_new_tokens=8 + 4 * (uid % 3))
            for uid in range(n)]


def run_monolithic(cfg, params, kstate, reqs, max_slots, max_len):
    eng = InferenceEngine(cfg, params, kstate, max_slots=max_slots,
                          max_len=max_len)
    out = eng.run(reqs)
    eng.close()
    return out


def run_prefill_pool(cfg, params, kstate, reqs, max_slots, max_len,
                     transport):
    """Prefill + export every request; returns the exported blob names."""
    eng = InferenceEngine(cfg, params, kstate, max_slots=max_slots,
                          max_len=max_len, prefill_only=True,
                          kvstore=KVStore(StoreConfig(remote=transport)))
    for r in reqs:
        eng.submit(r)
    while eng.has_work():
        eng.step()
    names = [eng.export_session(r.uid) for r in reqs
             if r.state == "PARKED"]
    eng.close()
    return names


def run_decode_pool(cfg, params, kstate, names, max_slots, max_len,
                    transport):
    eng = InferenceEngine(cfg, params, kstate, max_slots=max_slots,
                          max_len=max_len,
                          kvstore=KVStore(StoreConfig(
                              remote=transport, async_transfers=True)))
    handles = [eng.import_session(n) for n in names]
    while eng.has_work():
        eng.step()
    eng.close()
    return {h.uid: h.output for h in handles}


def single_process(args) -> int:
    cfg, params, kstate = build_model(small=args.small)
    max_slots, max_len = 2, 128
    reqs = make_requests(cfg)
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params; "
          f"{len(reqs)} requests, prefill pool -> decode pool")

    ref = run_monolithic(cfg, params, kstate, make_requests(cfg),
                         max_slots, max_len)
    server = TCPStoreServer() if args.tcp else None
    try:
        if args.tcp:
            mk = lambda: TCPTransport(server.host, server.port)
            print(f"transport: tcp localhost:{server.port}")
        else:
            loop = LoopbackTransport()
            mk = lambda: loop
            print("transport: loopback")
        names = run_prefill_pool(cfg, params, kstate, reqs, max_slots,
                                 max_len, mk())
        print(f"prefill pool exported {len(names)} sessions")
        out = run_decode_pool(cfg, params, kstate, names, max_slots,
                              max_len, mk())
    finally:
        if server is not None:
            server.close()
    for r in reqs:                      # finished during prefill (eos)
        out.setdefault(r.uid, list(r.output))
    identical = out == ref
    for uid in sorted(out):
        print(f"  uid {uid}: {out[uid]}")
    print(f"bit-identical to monolithic engine: {identical}")
    return 0 if identical else 1


def role_prefill(args) -> int:
    host, port = args.connect.rsplit(":", 1)
    transport = TCPTransport(host, int(port))
    print(f"prefill pool: waiting for decode peer at {host}:{port}")
    transport.wait_until_ready(timeout_s=120)
    cfg, params, kstate = build_model(small=args.small)
    max_slots, max_len = 2, 128
    reqs = make_requests(cfg)
    expected = run_monolithic(cfg, params, kstate, make_requests(cfg),
                              max_slots, max_len)
    names = run_prefill_pool(cfg, params, kstate, reqs, max_slots,
                             max_len, transport)
    for r in reqs:                      # finished during prefill (eos)
        if r.uid not in {int(n.rsplit("/", 1)[1]) for n in names}:
            expected.pop(r.uid, None)
    manifest = {"sessions": names,
                "expected": {str(u): t for u, t in expected.items()}}
    transport.put(MANIFEST, json.dumps(manifest).encode())
    print(f"prefill pool: exported {len(names)} sessions + manifest")
    return 0


def role_decode(args) -> int:
    server = TCPStoreServer(port=args.port)
    transport = TCPTransport(server.host, server.port)
    print(f"decode pool: blob peer listening on {server.host}:{server.port}")
    cfg, params, kstate = build_model(small=args.small)  # overlaps the wait
    deadline = time.monotonic() + args.timeout_s
    while not transport.exists(MANIFEST):
        if time.monotonic() > deadline:
            print("FAIL: no manifest arrived before the timeout",
                  file=sys.stderr)
            server.close()
            return 1
        time.sleep(0.25)
    manifest = json.loads(transport.get(MANIFEST).decode())
    names = manifest["sessions"]
    expected = {int(u): t for u, t in manifest["expected"].items()}
    print(f"decode pool: importing {len(names)} sessions")
    out = run_decode_pool(cfg, params, kstate, names, 2, 128, transport)
    server.close()
    identical = out == expected
    print(f"decode pool: token streams bit-identical to the prefill "
          f"process's monolithic reference: {identical}")
    return 0 if identical else 1


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tcp", action="store_true",
                    help="single process, but through a localhost TCP peer")
    ap.add_argument("--role", choices=("prefill", "decode"), default=None,
                    help="two-process mode: which pool this process is")
    ap.add_argument("--port", type=int, default=0,
                    help="decode role: port for the blob peer (0=ephemeral)")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="prefill role: the decode process's blob peer")
    ap.add_argument("--timeout-s", type=float, default=300.0,
                    help="decode role: how long to wait for the manifest")
    ap.add_argument("--small", action="store_true",
                    help="tiny model (CI two-process smoke)")
    args = ap.parse_args(argv)
    if args.role == "prefill":
        if not args.connect:
            ap.error("--role prefill needs --connect HOST:PORT")
        return role_prefill(args)
    if args.role == "decode":
        return role_decode(args)
    return single_process(args)


if __name__ == "__main__":
    sys.exit(main())
