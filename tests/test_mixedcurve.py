"""One org on Ed25519 (PR 32): a channel whose orgs sign on two curves,
from provisioning to the committed flags.

Provisioning with a per-org scheme; the Ed25519 pack against the plain
reference (`crypto/_ed25519.py`, RFC 8032 in Python ints); a block that
mixes P-256 and Ed25519 endorsers and creators, tampered on each curve,
through the validator's three tails with the software provider (OpenSSL)
and with the device provider on the CPU backend; the provider's warm-up
for both kernel families.
"""

import hashlib
import json
import random

import numpy as np
import pytest

from fabric_tpu.bccsp import SCHEME_ED25519, SCHEME_P256, VerifyItem
from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.bccsp.sw import SoftwareProvider
from fabric_tpu.committer import PolicyRegistry, TxValidator
from fabric_tpu.crypto import _ed25519 as plain
from fabric_tpu.node.orderer import load_signing_identity
from fabric_tpu.node.provision import provision_network
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Envelope, KVWrite, NsRwSet, TxRwSet,
                                 ValidationCode, build)
from fabric_tpu.testing.procnet import load_client

ORGS = ("Org1", "Org2", "Org3")
VALID = int(ValidationCode.VALID)
POLICY_FAILURE = int(ValidationCode.ENDORSEMENT_POLICY_FAILURE)
BAD_CREATOR = int(ValidationCode.BAD_CREATOR_SIGNATURE)


@pytest.fixture(scope="module", autouse=True)
def sw_default():
    return init_factories(FactoryOpts(default="SW"))


def load_identity(path):
    with open(path) as f:
        cfg = json.load(f)
    return load_signing_identity(cfg["mspid"], cfg["cert_pem"].encode(),
                                 cfg["key_pem"].encode())


@pytest.fixture(scope="module")
def network(tmp_path_factory):
    """and3's channel with every identity of Org3 on Ed25519."""
    base = str(tmp_path_factory.mktemp("mixedcurve"))
    net = provision_network(base, n_orderers=1, peer_orgs=ORGS,
                            clients_per_org=2,
                            org_schemes={"Org3": SCHEME_ED25519})
    peers = [load_identity(p) for p in net["peers"]]
    clients = {org: [load_identity(p) for p in net["client_pool"][org]]
               for org in ORGS}
    _, _, msps = load_client(net["clients"]["Org1"])
    return {"peers": peers, "clients": clients, "msps": msps}


# -- provisioning ----------------------------------------------------------------

def test_a_per_org_scheme_reaches_peers_and_clients(network):
    schemes = {p.mspid: p.scheme for p in network["peers"]}
    assert schemes == {"Org1": SCHEME_P256, "Org2": SCHEME_P256,
                       "Org3": SCHEME_ED25519}
    for org, pool in network["clients"].items():
        assert len(pool) == 2
        assert {c.scheme for c in pool} == {schemes[org]}


def test_the_channels_msps_validate_both_curves(network):
    """One P-256 org CA signs leaves of either scheme, and every peer's
    endorsement verifies under the identity the channel deserializes."""
    from fabric_tpu.msp import deserialize_from_msps
    everyone = network["peers"] + [c for pool in network["clients"].values()
                                   for c in pool]
    for signer in everyone:
        ident = deserialize_from_msps(network["msps"], signer.serialize())
        assert ident is not None and ident.scheme == signer.scheme
        assert network["msps"][signer.mspid].is_valid(ident)
        sig = signer.sign(b"endorsed bytes")
        assert len(sig) == 64 or signer.scheme == SCHEME_P256
        assert ident.verify(b"endorsed bytes", sig)
        assert not ident.verify(b"other bytes", sig)


def test_a_scheme_for_an_org_that_is_not_there_is_refused(tmp_path):
    with pytest.raises(ValueError):
        provision_network(str(tmp_path), n_orderers=1, peer_orgs=("Org1",),
                          org_schemes={"Org9": SCHEME_ED25519})


# -- the pack against the plain reference ------------------------------------------

def value_of(words_col) -> int:
    return int.from_bytes(b"".join(int(w).to_bytes(4, "big")
                                   for w in words_col), "big")


@pytest.mark.parametrize("size", [0, 1, 63, 64, 111, 112, 128, 1100, 2047,
                                  2048, 3500, 4096])
def test_pack_verify_inputs_against_the_plain_reference(size):
    """k = SHA-512(R || A || M) mod L stays exact, and R, S, A reach the
    kernel as the integers RFC 8032 encodes, on messages of 0-4 KB."""
    from fabric_tpu.ops import ed25519 as edops
    from fabric_tpu.ops import edwards
    rng = random.Random(size)
    pks, sigs, msgs = [], [], []
    for i in range(5):
        seed = rng.randbytes(32)
        msg = rng.randbytes(size)
        pks.append(plain.public_from_seed(seed))
        sigs.append(plain.sign(seed, msg))
        msgs.append(msg)
        assert plain.verify(pks[-1], sigs[-1], msg)
    ay, a_sign, ry, r_sign, s, k = edops.pack_verify_inputs(pks, sigs, msgs)
    top = (1 << 255) - 1
    for j in range(5):
        a = int.from_bytes(pks[j], "little")
        r = int.from_bytes(sigs[j][:32], "little")
        want_k = int.from_bytes(hashlib.sha512(
            sigs[j][:32] + pks[j] + msgs[j]).digest(), "little") % edwards.L
        assert value_of(k[:, j]) == want_k
        assert value_of(s[:, j]) == int.from_bytes(sigs[j][32:], "little")
        assert (value_of(ry[:, j]), int(r_sign[j])) == (r & top, r >> 255)
        assert (value_of(ay[:, j]), int(a_sign[j])) == (a & top, a >> 255)


def test_pack_refuses_malformed_lengths():
    from fabric_tpu.ops import ed25519 as edops
    with pytest.raises(ValueError):
        edops.pack_verify_inputs([b"\x00" * 31], [b"\x00" * 64], [b""])
    with pytest.raises(ValueError):
        edops.pack_verify_inputs([b"\x00" * 32], [b"\x00" * 63], [b""])
    assert edops.pack_verify_inputs([], [], [])[5].shape == (8, 0)


# -- a mixed block through the validator ---------------------------------------------

def flip(sig: bytes) -> bytes:
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


def mixed_block(network, n_txs=12):
    """`n_txs` transactions under AND(Org1, Org2, Org3), creators of all
    three orgs in turn (so of both curves); one endorsement broken on
    each curve, one creator signature broken on each curve.  -> (block,
    expected codes)"""
    peers = network["peers"]
    creators = [network["clients"][org][i] for i in range(2) for org in ORGS]
    envs, want = [], []
    for t in range(n_txs):
        creator = creators[t % len(creators)]
        rwset = TxRwSet((NsRwSet("cc", writes=(
            KVWrite(f"k{t}", b"v" * (1 + 97 * (t % 12))),)),))
        env = build.endorser_tx("ch", "cc", "1.0", rwset, creator, peers)
        code = VALID
        if t in (3, 4):                 # Org2's (P-256), Org3's (Ed25519)
            tx = env.payload_dict()["data"]
            e = tx["actions"][0]["endorsements"][t - 2]
            e["signature"] = flip(e["signature"])
            env = build.signed_envelope("endorser_transaction", "ch", tx,
                                        creator)
            code = POLICY_FAILURE
        if t in (7, 8):                 # a P-256 creator, an Ed25519 one
            env = Envelope(env.payload, flip(env.signature))
            code = BAD_CREATOR
        envs.append(env)
        want.append(code)
    assert {creators[7 % 6].scheme, creators[8 % 6].scheme} == {
        SCHEME_P256, SCHEME_ED25519}
    return build.new_block(0, b"\x00" * 32, envs), want


def validator_for(network, provider, python_tail=False):
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy(
        "AND('Org1.member','Org2.member','Org3.member')"))
    v = TxValidator("ch", network["msps"], provider, policies)
    if python_tail:
        v.force_python_collect = True
    return v


def test_a_mixed_block_gets_the_same_flags_on_every_tail(network):
    """The deep C tail interns Ed25519 items itself (over the message,
    not a digest); the Python tail must see the same items and give the
    same flags, and both the plain reference's (OpenSSL)."""
    block, want = mixed_block(network)
    sw = SoftwareProvider()
    deep = validator_for(network, sw).validate(block)
    classic = validator_for(network, sw, python_tail=True).validate(block)
    assert deep.flags.codes() == classic.flags.codes() == want
    assert deep.n_unique_items == classic.n_unique_items == 48
    # what reached the provider: a third of the items carry a message
    seen = []

    class Spy(SoftwareProvider):
        def batch_verify(self, items):
            seen.extend(items)
            return super().batch_verify(items)
    validator_for(network, Spy()).validate(block)
    by_scheme = {s: [it for it in seen if it.scheme == s]
                 for s in (SCHEME_P256, SCHEME_ED25519)}
    assert len(by_scheme[SCHEME_ED25519]) == 12 + 4
    assert len(by_scheme[SCHEME_P256]) == 24 + 8
    assert all(type(it) is VerifyItem and len(it.pubkey) == 32
               and len(it.payload) > 64 for it in by_scheme[SCHEME_ED25519])
    assert all(len(it.payload) == 32 for it in by_scheme[SCHEME_P256])


def test_a_big_mixed_block_is_a_table_and_a_short_list(network,
                                                       device_provider,
                                                       handed_over):
    """Above the validator's PROBE the deep tail hands over P-256 as the
    table's rows and Ed25519 as the VerifyItems beside it: two thirds
    and one third of the block's unique items, every one at the position
    the Python tail gives it, every verdict at its position — the
    tampered Org3 endorsement's and the broken Ed25519 creator's among
    them — from the software provider (which builds the items) and from
    the device provider (which packs the rows as they are)."""
    from fabric_tpu.committer.txvalidator import PROBE
    n_txs = 72
    block, want = mixed_block(network, n_txs)
    sw = SoftwareProvider()
    classic = validator_for(network, sw, python_tail=True)
    state = classic.validate_begin(block)
    order = list(state["items"])                 # the Python tail's
    assert classic.validate_finish(state).flags.codes() == want
    assert len(order) == 4 * n_txs > PROBE

    seen = []

    class Spy(SoftwareProvider):
        def batch_verify_packed_async(self, batch):
            seen.append(batch)
            return super().batch_verify_packed_async(batch)

    before = handed_over()
    v = validator_for(network, Spy())
    state = v.validate_begin(block)
    table = state["items"]
    assert v.validate_finish(state).flags.codes() == want
    assert seen == [table] and list(table) == order
    assert (table.n_rows, len(table.rest)) == (192, 96)
    assert handed_over(before) == {
        ("arrays", "bypassed"): 192, ("items", "scheme"): 96}
    pos = np.frombuffer(table.pos, np.int32).tolist()
    rest_pos = np.frombuffer(table.rest_pos, np.int32).tolist()
    assert sorted(pos + rest_pos) == list(range(4 * n_txs))
    assert all(order[p].scheme == SCHEME_P256 for p in pos)
    assert [order[p] for p in rest_pos] == table.rest
    assert all(type(it) is VerifyItem and it.scheme == SCHEME_ED25519
               for it in table.rest)
    assert table.digest == b"".join(order[p].payload for p in pos)
    assert len(table.keys) == 6 and all(len(k) == 65 for k in table.keys)

    truth = sw.batch_verify(order)
    assert truth.tolist().count(False) == 4
    broken = [order[i].scheme for i in np.nonzero(~truth)[0]]
    assert sorted(broken) == sorted([SCHEME_P256, SCHEME_ED25519] * 2)

    tpu = device_provider
    before = dict(tpu.stats)
    assert tpu.batch_verify_packed_async(table)().tolist() == truth.tolist()
    assert tpu.stats["dispatches"] - before["dispatches"] == 2
    assert tpu.stats["device_sigs"] - before["device_sigs"] == 4 * n_txs
    assert tpu.stats["fast_key_sigs"] - before["fast_key_sigs"] == 4 * n_txs
    assert tpu.stats["fallbacks"] == 0 and tpu.stats["host_rejects"] == 0


@pytest.fixture(scope="module")
def device_provider():
    """The device provider on the CPU backend; every key earns a table,
    so a block is one `rows` and one `ed25519-rows` dispatch."""
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    return JaxTpuProvider(fast_key_threshold=1, max_cached_keys=16)


def test_warm_names_both_kernel_families_and_a_mixed_block_compiles_nothing(
        network, device_provider):
    """`warm` for the Ed25519 shapes, then a mixed block through the
    validator on the device provider: the flags are the software
    provider's, two programs ran, and nothing compiled after the
    warm-up."""
    from fabric_tpu.bccsp.jaxtpu import COMPILE_STATS
    tpu = device_provider
    # the block's shapes: 2 endorsers + 4 creators on P-256 are 6 rows
    # (bucket 16), 1 endorser + 2 creators on Ed25519 are 3 (bucket 4)
    timings = tpu.warm(rows=[16], ed25519_rows=[4])
    assert sorted(timings) == ["ed25519-rows@4", "rows@16"]
    with pytest.raises(ValueError):
        tpu.warm(ed25519_rows=[5])
    with pytest.raises(ValueError):
        tpu.warm(ed25519=[300])
    compiles, before = COMPILE_STATS["compiles"], dict(tpu.stats)
    block, want = mixed_block(network)
    result = validator_for(network, tpu).validate(block)
    assert result.flags.codes() == want
    assert COMPILE_STATS["compiles"] == compiles
    assert tpu.stats["dispatches"] - before["dispatches"] == 2
    assert tpu.stats["device_sigs"] - before["device_sigs"] == 48
    assert tpu.stats["fast_key_sigs"] - before["fast_key_sigs"] == 48
    assert tpu.stats["fallbacks"] == 0 and tpu.stats["host_rejects"] == 0


def test_the_device_provider_agrees_with_openssl_item_by_item(
        device_provider):
    """Seeded Ed25519 items, sound and broken in every field, on both
    Ed25519 lanes' host paths: the verdicts are OpenSSL's."""
    sw, tpu = SoftwareProvider(), device_provider
    rng = random.Random(32)
    keys = [sw.key_gen(SCHEME_ED25519) for _ in range(3)]
    items = []
    for i in range(18):
        key = keys[i % 3]
        msg = rng.randbytes(rng.randrange(0, 4096))
        items.append(VerifyItem(SCHEME_ED25519, key.public_bytes(),
                                sw.sign(key, msg), msg))
    good = items[0]
    items += [
        good._replace(signature=flip(good.signature)),          # S
        good._replace(signature=bytes([good.signature[0] ^ 1])
                      + good.signature[1:]),                    # R
        good._replace(payload=good.payload + b"x"),             # M
        good._replace(pubkey=keys[1].public_bytes()),           # A
        good._replace(signature=good.signature[:32] + b"\xff" * 32),  # S >= L
        good._replace(signature=good.signature[:63]),           # length
        good._replace(pubkey=good.pubkey[:31]),                 # length
    ]
    rng.shuffle(items)
    want = sw.batch_verify(items)
    got = tpu.batch_verify(items)
    assert got.tolist() == want.tolist()
    assert want.sum() == 18
