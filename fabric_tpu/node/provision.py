"""Dev-network provisioning: crypto material + node configs on disk.

The composition of the reference's cryptogen + configtxgen
(/root/reference/internal/cryptogen, internal/configtxgen): generates an
orderer org, per-node signing identities, the channel's genesis
ChannelConfig, and one JSON config file per orderer process, ready for
`python -m fabric_tpu.node.orderer <node.json>`.
"""

from __future__ import annotations

import json
import os
from typing import List, Tuple

from fabric_tpu.crypto import serialization

from fabric_tpu.config import BatchConfig, ChannelConfig, OrgConfig, default_policies
from fabric_tpu.msp.ca import DevOrg


def _key_pem(key) -> bytes:
    return key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption())


def _cert_pem(cert) -> bytes:
    return cert.public_bytes(serialization.Encoding.PEM)


def provision_orderers(base_dir: str, n: int, channel_id: str = "ch",
                       base_port: int = 0,
                       batch: BatchConfig = None) -> List[str]:
    """Create material for an n-node orderer cluster; returns the list of
    node-config paths.  base_port=0 lets the OS pick ports (they are
    reserved by binding momentarily, then released)."""
    import socket

    org = DevOrg("OrdererOrg")
    mc = org.msp_config()

    ports = []
    socks = []
    for i in range(n):
        if base_port:
            ports.append(base_port + i)
        else:
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
    for s in socks:
        s.close()

    # issue every consenter identity first so both the channel config and
    # the shared cluster list can bind raft ids to certificate
    # fingerprints (not forgeable CN strings)
    from fabric_tpu.orderer.cluster import cert_fingerprint

    creds = [org.issuer.issue(f"orderer{i + 1}@OrdererOrg") for i in range(n)]
    cluster = [{"raft_id": i + 1, "host": "127.0.0.1", "port": ports[i],
                "mspid": "OrdererOrg",
                "cert_fp": cert_fingerprint(creds[i][0])}
               for i in range(n)]

    cfg = ChannelConfig(
        channel_id=channel_id,
        sequence=0,
        orgs=(OrgConfig(mspid="OrdererOrg",
                        root_certs=tuple(mc.root_certs_pem),
                        admins=tuple(mc.admin_certs_pem)),),
        policies=default_policies(["OrdererOrg"]),
        batch=batch or BatchConfig(max_message_count=2, timeout_s=0.2),
        consenters=tuple(cluster),
    )
    cfg_hex = cfg.serialize().hex()
    paths = []
    for i in range(n):
        node_dir = os.path.join(base_dir, f"orderer{i + 1}")
        os.makedirs(node_dir, exist_ok=True)
        cert, key = creds[i]
        node_cfg = {
            "mspid": "OrdererOrg",
            "raft_id": i + 1,
            "host": "127.0.0.1",
            "port": ports[i],
            "cert_pem": _cert_pem(cert).decode(),
            "key_pem": _key_pem(key).decode(),
            "channel_config_hex": cfg_hex,
            "cluster": cluster,
            "data_dir": node_dir,
        }
        path = os.path.join(base_dir, f"orderer{i + 1}.json")
        with open(path, "w") as f:
            json.dump(node_cfg, f)
        paths.append(path)

    # client material (for tests/tools): one member + the org admin
    client_cert, client_key = org.issuer.issue("client@OrdererOrg")
    with open(os.path.join(base_dir, "client.json"), "w") as f:
        json.dump({
            "mspid": "OrdererOrg",
            "cert_pem": _cert_pem(client_cert).decode(),
            "key_pem": _key_pem(client_key).decode(),
            "channel_config_hex": cfg_hex,
            "cluster": cluster,
            "channel_id": channel_id,
        }, f)
    with open(os.path.join(base_dir, "admin.json"), "w") as f:
        json.dump({
            "mspid": "OrdererOrg",
            "cert_pem": _cert_pem(org.admin.cert).decode(),
            "key_pem": _key_pem(org.admin._key.key).decode(),
            "channel_config_hex": cfg_hex,
            "cluster": cluster,
            "channel_id": channel_id,
        }, f)
    return paths


# -- the roll: enrolment at the scale of an application's users ---------------

ROLL_CHUNK = 4096    # members issued in one task; a roll larger than one
                     # chunk is issued by a pool of processes


def issue_roll_chunk(ca_cert_pem: bytes, ca_key_pem: bytes, mspid: str,
                     scheme, first: int, count: int) -> Tuple[list, list]:
    """Members `first` .. `first + count` of one org's roll, enrolled
    under the org's CA (common name `user<j>@<org>`, as one
    `registerAndEnrollUser` a user would leave): (certificates, keys),
    PEM strings.  A task of `enrol_roll`'s pool, so it takes the CA as
    PEM and builds the issuer again."""
    from fabric_tpu.msp.ca import CA
    issuer = CA.load(ca_cert_pem, ca_key_pem)
    certs, keys = [], []
    for j in range(first, first + count):
        cert, key = issuer.issue(f"user{j}@{mspid}", scheme=scheme)
        certs.append(_cert_pem(cert).decode())
        keys.append(_key_pem(key).decode())
    return certs, keys


def roll_member(index: int, n_orgs: int) -> Tuple[int, int]:
    """(org position, number within the org's roll) of roll member
    `index`: org by org in turn, as the pooled clients are dealt."""
    return index % n_orgs, index // n_orgs


def enrol_roll(base_dir: str, orgs: dict, org_schemes: dict, size: int,
               revoked) -> Tuple[dict, dict]:
    """Enrol `size` clients over `orgs` ({name: DevOrg}, in order), org
    by org in turn, and revoke the members `revoked` names (roll
    indices): ({org: path of its roll}, {org: its CRL as PEM, signed by
    its CA — none for an org with nobody revoked}).  One roll per org:
    `roll_<org>.json` = {"mspid", "cert_pem": [...], "key_pem": [...],
    "revoked": [numbers within the org]} — a certificate and a key a
    member, not a client config each."""
    from fabric_tpu.crypto import x509
    names = list(orgs)
    gone = {name: [] for name in names}      # before anything is issued
    for index in sorted(set(revoked)):
        if not 0 <= index < size:
            raise ValueError(f"revoked member {index} is not on a roll "
                             f"of {size}")
        k, j = roll_member(index, len(names))
        gone[names[k]].append(j)
    per_org = [len(range(k, size, len(names))) for k in range(len(names))]
    tasks = [(name, first, min(ROLL_CHUNK, n - first))
             for name, n in zip(names, per_org)
             for first in range(0, n, ROLL_CHUNK)]

    def args_of(name, first, count):
        ca = orgs[name].issuer
        return (ca.cert_pem(), _key_pem(ca._key), name,
                org_schemes.get(name), first, count)

    if size > ROLL_CHUNK:
        # spawned workers import the caller's `__main__`: a script that
        # enrols a roll this large needs its `if __name__ == "__main__"`
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor
        with ProcessPoolExecutor(
                max_workers=min(len(tasks), os.cpu_count() or 1, 8),
                mp_context=multiprocessing.get_context("spawn")) as pool:
            chunks = list(pool.map(issue_roll_chunk,
                                   *zip(*(args_of(*t) for t in tasks))))
    else:
        chunks = [issue_roll_chunk(*args_of(*t)) for t in tasks]
    certs = {name: [] for name in names}
    keys = {name: [] for name in names}
    for (name, _first, _count), (c, k) in zip(tasks, chunks):
        certs[name].extend(c)
        keys[name].extend(k)
    paths, crls = {}, {}
    for name in names:
        paths[name] = os.path.join(base_dir, f"roll_{name}.json")
        with open(paths[name], "w") as f:
            json.dump({"mspid": name, "cert_pem": certs[name],
                       "key_pem": keys[name], "revoked": gone[name]}, f)
        if gone[name]:
            crls[name] = orgs[name].issuer.crl(
                [x509.load_pem_x509_certificate(certs[name][j].encode())
                 for j in gone[name]])
    return paths, crls


def free_ports(n: int) -> List[int]:
    """n ports the OS just handed out (bound momentarily, released)."""
    import socket
    ports, socks = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def provision_network(base_dir: str, n_orderers: int = 3,
                      peer_orgs: List[str] = ("Org1", "Org2"),
                      peers_per_org: int = 1,
                      channel_id: str = "ch",
                      chaincodes: List[dict] = None,
                      collections: List[dict] = None,
                      batch: BatchConfig = None,
                      spare_orderers: int = 0,
                      clients_per_org: int = 1,
                      org_schemes: dict = None,
                      roll_size: int = 0,
                      roll_revoked=()) -> dict:
    """Full dev network: orderer cluster + peer-org peers on one channel.

    The nwo-style harness (reference: integration/nwo/network.go:173) —
    generates all crypto material and one JSON config per process.
    Returns {"orderers": [cfg paths], "peers": [cfg paths],
             "clients": {org: client cfg path}}.

    `spare_orderers`: additionally issues N orderer identities + node
    configs that are NOT in the genesis consenter set — provisioned but
    unjoined, the raw material for dynamic-membership drills (an
    add-consenter config entry carries the spare's binding to everyone).
    Their cfg paths land under "spare_orderers"; each cfg carries its
    own "cert_fp" so a drill can build the add_consenter request
    without re-deriving it.

    `clients_per_org`: enrolled client identities per peer org.
    "clients" keeps naming each org's first; all of them are listed
    under "client_pool" ({org: [cfg paths]}).

    `org_schemes`: {org: signature scheme} for the peer orgs that have
    re-enrolled — every peer and every pooled client of such an org
    holds a key of that scheme (SCHEME_ED25519) under the org's own CA,
    the state of a Fabric v3 channel (capability V3_0) whose orgs move
    to Ed25519 one at a time.  An org not named signs P-256, as does
    every org CA and admin: the channel's MSPs accept both either way.

    `roll_size`: besides the pooled clients, enrol that many clients
    over the peer orgs, org by org in turn — the account holders of an
    application whose users are Fabric identities (one enrolment
    certificate each from the org's CA).  They are written as one roll
    per org ("rolls": {org: path}, `enrol_roll`), not as a client
    config each.  `roll_revoked`: the roll members (indices) whose
    certificates are revoked: each org's CRL, signed by its CA, is in
    that org's MSP in the genesis channel config, as `fabric-ca-client
    revoke` + `gencrl` + a config update would leave it.
    """
    from fabric_tpu.orderer.cluster import cert_fingerprint

    org_schemes = dict(org_schemes or {})
    unknown = set(org_schemes) - set(peer_orgs)
    if unknown:
        raise ValueError(f"org_schemes names no peer org: {sorted(unknown)}")
    ord_org = DevOrg("OrdererOrg")
    p_orgs = {name: DevOrg(name) for name in peer_orgs}
    all_orgs = {"OrdererOrg": ord_org, **p_orgs}

    n_peers = len(p_orgs) * peers_per_org
    ports = free_ports(n_orderers + n_peers + spare_orderers)
    ord_ports = ports[:n_orderers]
    peer_ports = ports[n_orderers:n_orderers + n_peers]
    spare_ports = ports[n_orderers + n_peers:]

    # the roll first: its CRLs are part of the channel config that every
    # node's file carries
    rolls, crls = ({}, {}) if not roll_size else enrol_roll(
        base_dir, p_orgs, org_schemes, int(roll_size), roll_revoked)
    org_cfgs = []
    for name, org in all_orgs.items():
        mc = org.msp_config()
        org_cfgs.append(OrgConfig(mspid=name,
                                  root_certs=tuple(mc.root_certs_pem),
                                  admins=tuple(mc.admin_certs_pem),
                                  crls=(crls[name],) if name in crls else ()))
    # consenter identities first: the channel config itself carries the
    # rich consenter entries (raft id -> addr + mspid + cert fingerprint)
    creds = [ord_org.issuer.issue(f"orderer{i + 1}@OrdererOrg")
             for i in range(n_orderers)]
    cluster = [{"raft_id": i + 1, "host": "127.0.0.1", "port": ord_ports[i],
                "mspid": "OrdererOrg",
                "cert_fp": cert_fingerprint(creds[i][0])}
               for i in range(n_orderers)]

    cfg = ChannelConfig(
        channel_id=channel_id,
        sequence=0,
        orgs=tuple(org_cfgs),
        policies=default_policies(list(all_orgs)),
        batch=batch or BatchConfig(max_message_count=8, timeout_s=0.2),
        consenters=tuple(cluster),
    )
    cfg_hex = cfg.serialize().hex()

    chaincodes = chaincodes or [
        {"name": "assets", "version": "1.0", "contract": "asset_demo",
         "policy": "AND(%s)" % ", ".join(
             f"'{o}.member'" for o in peer_orgs)}]
    collections = collections or []

    # peer identities first: every peer hosts a gateway whose
    # handshake-verified transport identity the orderers pin as a
    # verdict-attestation attestor — trusting attestations is OFF by
    # node default, so the dev provisioner opts in EXPLICITLY with the
    # exact (mspid, cert sha256) bindings allowed to vouch
    peer_list = []
    idx = 0
    for org_name in peer_orgs:
        for j in range(peers_per_org):
            peer_list.append((org_name, j, peer_ports[idx]))
            idx += 1
    peer_creds = {(o, j): p_orgs[o].issuer.issue(
        f"peer{j}@{o}", scheme=org_schemes.get(o)) for o, j, _ in peer_list}
    attestors = [{"mspid": o, "cert_fp": cert_fingerprint(c)}
                 for (o, _), (c, _k) in peer_creds.items()]

    # orderers
    orderer_paths = []
    for i in range(n_orderers):
        node_dir = os.path.join(base_dir, f"orderer{i + 1}")
        os.makedirs(node_dir, exist_ok=True)
        cert, key = creds[i]
        path = os.path.join(base_dir, f"orderer{i + 1}.json")
        with open(path, "w") as f:
            json.dump({
                "mspid": "OrdererOrg", "raft_id": i + 1,
                "host": "127.0.0.1", "port": ord_ports[i],
                "cert_pem": _cert_pem(cert).decode(),
                "key_pem": _key_pem(key).decode(),
                "channel_config_hex": cfg_hex,
                "cluster": cluster, "data_dir": node_dir,
                "verify_once": {"trust_attestations": True,
                                "attestors": attestors,
                                "attest_deliver": True},
            }, f)
        orderer_paths.append(path)

    # spare orderers: identity + config on disk, EXCLUDED from the
    # genesis consenter tuple and every bootstrap cluster list.  A
    # spare that starts up is a silent learner (its raft node refuses
    # to campaign while outside the consenter set) until a committed
    # add-consenter config entry teaches the whole channel its binding.
    spare_paths = []
    spare_creds = [ord_org.issuer.issue(
        f"orderer{n_orderers + s + 1}@OrdererOrg")
        for s in range(spare_orderers)]
    for s in range(spare_orderers):
        rid = n_orderers + s + 1
        node_dir = os.path.join(base_dir, f"orderer{rid}")
        os.makedirs(node_dir, exist_ok=True)
        cert, key = spare_creds[s]
        path = os.path.join(base_dir, f"orderer{rid}.json")
        with open(path, "w") as f:
            json.dump({
                "mspid": "OrdererOrg", "raft_id": rid,
                "host": "127.0.0.1", "port": spare_ports[s],
                "cert_pem": _cert_pem(cert).decode(),
                "key_pem": _key_pem(key).decode(),
                "cert_fp": cert_fingerprint(cert),
                "channel_config_hex": cfg_hex,
                "cluster": cluster, "data_dir": node_dir,
                "verify_once": {"trust_attestations": True,
                                "attestors": attestors,
                                "attest_deliver": True},
            }, f)
        spare_paths.append(path)

    # the reverse direction: peers pin the orderer identities so the
    # admission-verdict digests riding deliver frames are honoured —
    # again an explicit dev-provisioner opt-in, off by node default.
    # Spares are pinned too: attestor trust is an identity allowlist,
    # not a membership statement, and a joined spare attests like any
    # other consenter.
    orderer_attestors = [{"mspid": "OrdererOrg",
                          "cert_fp": cert_fingerprint(c)}
                         for c, _k in creds + spare_creds]

    # peers: each knows every OTHER peer's endpoint + org (privdata push,
    # discovery membership)
    peer_paths = []
    for org_name, j, port in peer_list:
        org = p_orgs[org_name]
        node_dir = os.path.join(base_dir, f"peer{org_name}_{j}")
        os.makedirs(node_dir, exist_ok=True)
        cert, key = peer_creds[(org_name, j)]
        others = [["127.0.0.1", p, o] for (o, k, p) in peer_list
                  if (o, k) != (org_name, j)]
        path = os.path.join(base_dir, f"peer{org_name}_{j}.json")
        with open(path, "w") as f:
            json.dump({
                "mspid": org_name, "channel_id": channel_id,
                "host": "127.0.0.1", "port": port,
                "cert_pem": _cert_pem(cert).decode(),
                "key_pem": _key_pem(key).decode(),
                "channel_config_hex": cfg_hex,
                # the full ordering-service roster INCLUDING spares:
                # endpoint knowledge is fleet provisioning, not
                # membership — a spare that later joins (and may even
                # lead) must be dialable, an unstarted one just fails
                # dial and the broadcast/deliver failover walks on
                "orderers": [["127.0.0.1", p]
                             for p in ord_ports + spare_ports],
                "peers": others,
                "chaincodes": chaincodes,
                "collections": collections,
                "data_dir": node_dir,
                "verify_once": {"trust_attestations": True,
                                "attestors": orderer_attestors},
            }, f)
        peer_paths.append(path)

    # per-org clients: one per signature scheme the MSP accepts, so
    # mixed-identity workloads (workload/scenarios.py) can blend P-256
    # and ed25519 creators against the same channel
    from fabric_tpu.bccsp import SCHEME_ED25519
    clients = {}
    clients_ed25519 = {}
    client_pool = {org_name: [] for org_name in p_orgs}

    def _write_client(org_name, common_name, scheme, file_name) -> str:
        ccert, ckey = p_orgs[org_name].issuer.issue(common_name,
                                                    scheme=scheme)
        path = os.path.join(base_dir, file_name)
        with open(path, "w") as f:
            json.dump({
                "mspid": org_name,
                "cert_pem": _cert_pem(ccert).decode(),
                "key_pem": _key_pem(ckey).decode(),
                "channel_config_hex": cfg_hex,
                "channel_id": channel_id,
                "orderers": [["127.0.0.1", p]
                             for p in ord_ports + spare_ports],
                "peers": [["127.0.0.1", p, o]
                          for (o, k, p) in peer_list],
            }, f)
        return path

    for org_name in p_orgs:
        scheme = org_schemes.get(org_name)
        clients[org_name] = _write_client(
            org_name, f"client@{org_name}", scheme,
            f"client_{org_name}.json")
        clients_ed25519[org_name] = _write_client(
            org_name, f"client@{org_name}", SCHEME_ED25519,
            f"client_{org_name}_{SCHEME_ED25519}.json")
        client_pool[org_name].append(clients[org_name])
        for i in range(1, clients_per_org):
            client_pool[org_name].append(_write_client(
                org_name, f"client{i}@{org_name}", scheme,
                f"client_{org_name}_{i}.json"))
    # per-org ADMIN identities (channel-config admin certs): the admin
    # CLI's install/join verbs are Admins-gated
    admins = {}
    for org_name, org in p_orgs.items():
        path = os.path.join(base_dir, f"admin_{org_name}.json")
        with open(path, "w") as f:
            json.dump({
                "mspid": org_name,
                "cert_pem": _cert_pem(org.admin.cert).decode(),
                "key_pem": _key_pem(org.admin._key.key).decode(),
                "channel_config_hex": cfg_hex,
                "channel_id": channel_id,
            }, f)
        admins[org_name] = path
    return {"orderers": orderer_paths, "peers": peer_paths,
            "spare_orderers": spare_paths,
            "clients": clients, "clients_ed25519": clients_ed25519,
            "client_pool": client_pool, "admins": admins, "rolls": rolls}
