"""kube-apiserver entry point.

Ref: cmd/kube-apiserver/app/server.go — here the generic server IS the
assembly (no aggregation layers yet); serves REST+watch on --port.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from ..apiserver.server import APIServer
from ..utils import gcpolicy


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="kube-apiserver")
    p.add_argument("--bind-address", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--data-dir", default=None,
                   help="enable WAL persistence (replayed on restart)")
    p.add_argument("--wal-sync", action="store_true",
                   help="fdatasync each transaction")
    p.add_argument("--wal-compact-bytes", type=int, default=64 << 20,
                   help="compact the WAL when it exceeds this size")
    p.add_argument("--token-auth-file", default=None,
                   help="CSV token,user[,uid],group1;group2 — enables authn "
                        "(+ default-deny RBAC; system:masters gets all)")
    p.add_argument("--audit-log-path", default=None,
                   help="append one JSON audit line per request")
    p.add_argument("--tls-cert-file", default=None)
    p.add_argument("--tls-private-key-file", default=None)
    p.add_argument("--client-ca-file", default=None,
                   help="verify client certs against this CA; their "
                        "CN/O become user/groups (x509 authn)")
    args = p.parse_args(argv)
    if args.client_ca_file and not args.tls_cert_file:
        # client certs can only arrive over TLS; without a serving cert
        # the CA would silently never be consulted and every request
        # would be rejected by default-deny RBAC
        p.error("--client-ca-file requires --tls-cert-file/"
                "--tls-private-key-file")
    store = None
    wal_file = None
    if args.data_dir:
        import os

        from ..state.store import Store
        from ..utils.metrics import StoreMetrics
        os.makedirs(args.data_dir, exist_ok=True)
        wal_file = os.path.join(args.data_dir, "store.wal")
        store = Store(wal_path=wal_file, wal_sync=args.wal_sync,
                      metrics=StoreMetrics())
    srv = APIServer(store=store, host=args.bind_address,
                    port=args.port, audit_log_path=args.audit_log_path,
                    tls_cert_file=args.tls_cert_file,
                    tls_key_file=args.tls_private_key_file,
                    client_ca_file=args.client_ca_file)
    if store is not None:
        # the store's own families (wal_*, store_lock_wait_seconds,
        # store_compaction_seconds) beside the request families
        srv.metrics.add_registry("store", store.metrics.registry)
    if args.client_ca_file and not args.token_auth_file:
        # x509-only authn: cert identities + default-deny RBAC
        from ..apiserver.auth import CertAuthenticator, RBACAuthorizer
        srv.authenticator = CertAuthenticator()
        authz = RBACAuthorizer()
        authz.grant("group:system:masters", ["*"], ["*"])
        authz.use_store(srv.client)
        srv.authorizer = authz
    if args.token_auth_file:
        from ..apiserver.auth import (RBACAuthorizer, TokenAuthenticator,
                                      UserInfo)
        authn = TokenAuthenticator()
        with open(args.token_auth_file) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                fields = [x.strip() for x in line.split(",")]
                if len(fields) < 2:
                    print(f"skipping malformed token line: {line!r}",
                          flush=True)
                    continue
                token, user = fields[0], fields[1]
                # 3 fields = token,user,groups; 4+ = token,user,uid,groups
                # (the reference's --token-auth-file CSV)
                groups_field = fields[3] if len(fields) >= 4 else (
                    fields[2] if len(fields) == 3 else "")
                authn.add(token, UserInfo(
                    user, tuple(g for g in groups_field.split(";") if g)))
        authz = RBACAuthorizer()
        # the bootstrap superuser binding (ref: system:masters)
        authz.grant("group:system:masters", ["*"], ["*"])
        # stored Role/ClusterRole(+Binding) objects feed the live policy
        authz.use_store(srv.client)
        if args.client_ca_file:
            from ..apiserver.auth import CertAuthenticator
            authn = CertAuthenticator(fallback=authn)
        srv.authenticator = authn
        srv.authorizer = authz
    # the collector stops re-walking the stored cluster: each full
    # collection freezes what survived it (utils/gcpolicy.py)
    srv.metrics.add_registry("gc", gcpolicy.install().registry)
    srv.start()
    compactor = None
    if store is not None:
        import os

        def compact_loop():
            # size-triggered compaction bounds replay time by live objects,
            # not total write history (the etcd snapshot analog)
            while not stop.wait(30.0):
                try:
                    if os.path.getsize(wal_file) > args.wal_compact_bytes:
                        store.compact()
                except Exception:
                    pass
        compactor = threading.Thread(target=compact_loop, daemon=True)
    if store is not None:
        # which WAL appender is live (native/walcore.cc or the python one):
        # a toolchain failure must be readable from the hub's own output
        print(f"wal {wal_file} native={store.wal_native}", flush=True)
    print(f"serving on {srv.address}", flush=True)
    stop = threading.Event()

    def shutdown(*_):
        stop.set()
    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)
    if compactor is not None:
        compactor.start()
    stop.wait()
    srv.stop()
    if store is not None:
        store.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
