"""Enrolment at the scale of an application's users: the provisioner's
roll and its CRLs in the channel config, what `MSP.validate` says of a
revoked, a forged and an expired member (and under which label it books
the time), the MSP caches' counters past their 100 entries, and the
validator's two tails on a block of creators it has not seen."""
import datetime
import json
import os
from collections import OrderedDict

import pytest

from fabric_tpu.bccsp import SCHEME_P256
from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.bccsp.sw import SigningKey
from fabric_tpu.committer import PolicyRegistry, TxValidator
from fabric_tpu.committer import txvalidator as tv
from fabric_tpu.config import Bundle, ChannelConfig
from fabric_tpu.crypto import ec, hashes, x509
from fabric_tpu.msp import CachedMSP, Principal
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.msp.cache import CACHE_SIZE
from fabric_tpu.msp.identity import SigningIdentity
from fabric_tpu.msp.msp import MSPValidationError
from fabric_tpu.node import provision
from fabric_tpu.node.orderer import load_signing_identity
from fabric_tpu.ops_plane import registry, tracing
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Endorsement, KVWrite, NsRwSet, TxRwSet,
                                 ValidationCode)
from fabric_tpu.protocol import build
from fabric_tpu.protocol.types import Block, BlockHeader, BlockMetadata

ORGS = ["Org1", "Org2", "Org3"]


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


def forge(victim: SigningIdentity) -> SigningIdentity:
    """An identity under `victim`'s subject and its CA's issuer name,
    with a key of the forger's own, signed by a key that is not the
    CA's."""
    rogue = ec.generate_private_key(ec.SECP256R1())
    key = ec.generate_private_key(ec.SECP256R1())
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(victim.cert.subject)
            .issuer_name(victim.cert.issuer)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=30))
            .add_extension(x509.BasicConstraints(ca=False, path_length=None),
                           critical=True)
            .sign(rogue, hashes.SHA256()))
    return SigningIdentity(victim.mspid, cert, SigningKey(SCHEME_P256, key))


def counted(name: str, **labels) -> float:
    """The sum over the process's exposition of the series `name` (a
    histogram's `<name>_count`) that carry `labels`."""
    total = 0.0
    for line in registry.expose_text().splitlines():
        if line.startswith((name + "{", name + "_count{")):
            head, value = line.rsplit(" ", 1)
            if all(f'{k}="{v}"' in head for k, v in labels.items()):
                total += float(value)
    return total


# -- the roll -------------------------------------------------------------------

ROLL, REVOKED = 300, [4, 5, 6, 299]      # Org2's 1, Org3's 1, Org1's 2, Org3's 99


@pytest.fixture(scope="module")
def network(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("roll"))
    net = provision.provision_network(base, peer_orgs=ORGS, roll_size=ROLL,
                                      roll_revoked=REVOKED)
    with open(net["peers"][0]) as f:
        cfg_hex = json.load(f)["channel_config_hex"]
    bundle = Bundle(ChannelConfig.deserialize(bytes.fromhex(cfg_hex)))
    rolls = {}
    for org, path in net["rolls"].items():
        with open(path) as f:
            rolls[org] = json.load(f)
    return base, net, bundle, rolls


def member(rolls, index: int) -> SigningIdentity:
    k, j = provision.roll_member(index, len(ORGS))
    roll = rolls[ORGS[k]]
    return load_signing_identity(roll["mspid"], roll["cert_pem"][j].encode(),
                                 roll["key_pem"][j].encode())


def test_a_roll_is_one_artefact_an_org(network):
    base, net, bundle, rolls = network
    assert sorted(net["rolls"]) == ORGS
    assert [len(rolls[o]["cert_pem"]) for o in ORGS] == [100, 100, 100]
    assert [len(rolls[o]["key_pem"]) for o in ORGS] == [100, 100, 100]
    assert [rolls[o]["revoked"] for o in ORGS] == [[2], [1], [1, 99]]
    # a certificate and a key a member, not a client config each
    assert sorted(f for f in os.listdir(base) if f.startswith("roll_")) == [
        f"roll_{o}.json" for o in ORGS]
    assert len([f for f in os.listdir(base) if f.startswith("client_")]) \
        == 2 * len(ORGS)
    assert all("channel_config_hex" not in rolls[o] for o in ORGS)
    assert member(rolls, 7).subject == "CN=user2@Org2"


def test_the_crls_are_in_the_channel_config(network):
    _base, net, bundle, _rolls = network
    crls = {o.mspid: o.crls for o in bundle.config.orgs}
    assert all(len(crls[o]) == 1 for o in ORGS) and crls["OrdererOrg"] == ()
    # every node's file carries the same genesis config
    configs = set()
    for path in net["peers"] + net["orderers"] + list(net["clients"].values()):
        with open(path) as f:
            configs.add(json.load(f)["channel_config_hex"])
    assert len(configs) == 1
    for org in ORGS:
        crl = x509.load_pem_x509_crl(crls[org][0])
        assert len(list(crl)) == {"Org1": 1, "Org2": 1, "Org3": 2}[org]


def test_the_pooled_clients_are_as_before(tmp_path):
    net = provision.provision_network(str(tmp_path), peer_orgs=ORGS[:2],
                                      clients_per_org=3)
    assert net["rolls"] == {}
    assert [len(net["client_pool"][o]) for o in ORGS[:2]] == [3, 3]
    with open(net["peers"][0]) as f:
        cfg = ChannelConfig.deserialize(
            bytes.fromhex(json.load(f)["channel_config_hex"]))
    assert all(o.crls == () for o in cfg.orgs)


def test_a_roll_beyond_one_chunk_is_issued_by_a_pool(tmp_path, monkeypatch):
    monkeypatch.setattr(provision, "ROLL_CHUNK", 16)
    net = provision.provision_network(str(tmp_path), peer_orgs=ORGS[:2],
                                      roll_size=70, roll_revoked=[3])
    with open(net["rolls"]["Org2"]) as f:
        roll = json.load(f)
    assert len(roll["cert_pem"]) == 35 and roll["revoked"] == [1]
    names = [load_signing_identity("Org2", c.encode(), k.encode()).subject
             for c, k in zip(roll["cert_pem"], roll["key_pem"])]
    assert names == [f"CN=user{j}@Org2" for j in range(35)]
    with pytest.raises(ValueError):
        provision.provision_network(str(tmp_path / "x"), peer_orgs=ORGS[:2],
                                    roll_size=10, roll_revoked=[10])


@pytest.mark.parametrize("case,result", [
    ("sound", "ok"), ("revoked", "revoked"), ("forged", "untrusted"),
    ("expired", "expired")])
def test_validate_books_its_time_under_the_result(network, case, result):
    _base, _net, bundle, rolls = network
    msp = bundle.msps["Org3"]
    if case == "sound":
        ident = member(rolls, 8)
    elif case == "revoked":
        ident = member(rolls, 299)
    elif case == "forged":
        ident = forge(member(rolls, 8))
    else:
        org = DevOrg("Org3")
        ident = org.new_identity(
            "late", not_after=datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(minutes=1))
        msp = CachedMSP(org.msp())
    n0 = counted("msp_validate_seconds", msp="Org3", result=result)
    others0 = counted("msp_validate_seconds", msp="Org3")
    if result == "ok":
        msp.validate(ident)
    else:
        with pytest.raises(MSPValidationError) as refused:
            msp.validate(ident)
        assert refused.value.reason == result
        # the cache answers the second time, with the same error
        with pytest.raises(MSPValidationError) as again:
            msp.validate(ident)
        assert again.value.reason == result
    assert msp.is_valid(ident) == (result == "ok")
    assert counted("msp_validate_seconds", msp="Org3", result=result) \
        == n0 + 1
    assert counted("msp_validate_seconds", msp="Org3") == others0 + 1


# -- the caches' counters ---------------------------------------------------------


class OldStats:
    """`CachedMSP` as it counted before the registry did: three LRUs of
    `CACHE_SIZE`, one `stats` dict — the model the counters are held
    to."""

    def __init__(self):
        self.lru = {op: OrderedDict() for op in ("deserialize", "validate",
                                                 "principal")}
        self.by_op = {op: {"hit": 0, "miss": 0} for op in self.lru}

    def ask(self, op: str, key) -> None:
        d = self.lru[op]
        if key in d:
            d.move_to_end(key)
            self.by_op[op]["hit"] += 1
            return
        self.by_op[op]["miss"] += 1
        d[key] = None
        if len(d) > CACHE_SIZE:
            d.popitem(last=False)


@pytest.fixture(scope="module")
def scripted():
    """A sequence past the caches' size: 120 identities once, the first
    ten again (evicted: misses), the last ten again (hits)."""
    mspid = "RollOrg"
    org = DevOrg(mspid)
    cmsp = CachedMSP(org.msp())
    idents = [org.new_identity(f"u{i}") for i in range(CACHE_SIZE + 20)]
    order = (list(range(len(idents))) + list(range(10))
             + list(range(len(idents) - 10, len(idents))))
    model = OldStats()
    before = {(op, r): counted("msp_cache_total", msp=mspid, op=op, result=r)
              for op in model.lru for r in ("hit", "miss")}
    principal = Principal.member(mspid)
    for i in order:
        raw = idents[i].serialize()
        ident = cmsp.deserialize_identity(raw)
        model.ask("deserialize", raw)
        cmsp.validate(ident)
        model.ask("validate", ident)
        assert cmsp.satisfies_principal(ident, principal)
        model.ask("principal", (ident, principal))
    moved = {k: counted("msp_cache_total", msp=mspid, op=k[0], result=k[1])
             - v for k, v in before.items()}
    return cmsp, model, moved


@pytest.mark.parametrize("op", ["deserialize", "validate", "principal"])
def test_cache_counters_equal_the_old_stats(scripted, op):
    _cmsp, model, moved = scripted
    assert CACHE_SIZE == 100
    assert model.by_op[op] == {"hit": 10, "miss": 130}
    assert {r: moved[(op, r)] for r in ("hit", "miss")} == model.by_op[op]


def test_cache_eviction_order_is_unchanged(scripted):
    cmsp, model, _moved = scripted
    assert list(cmsp._deser._d) == list(model.lru["deserialize"])
    assert list(cmsp._valid._d) == list(model.lru["validate"])
    assert list(cmsp._princ._d) == list(model.lru["principal"])
    assert len(cmsp._valid._d) == CACHE_SIZE


# -- both tails on a block of unseen creators -------------------------------------

N_TX = 60
REVOKED_AT, FORGED_AT, TAMPERED_AT = (3, 17, 41), (5, 30), (9, 19, 29, 41)


@pytest.fixture(scope="module")
def unseen_block(sw_provider):
    """(raws, expected codes, msps, policies): 60 transactions, each
    from a creator of its own; three creators revoked by Org1's CRL, two
    forged, four endorsements tampered (one of them on a revoked
    creator's transaction: refused for the creator first)."""
    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    creators = [org1.new_identity(f"user{i}") for i in range(N_TX)]
    crl = org1.issuer.crl([creators[i].cert for i in REVOKED_AT])
    msps = {"Org1": CachedMSP(org1.msp(crls_pem=[crl])),
            "Org2": CachedMSP(org2.msp())}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    endorsers = [org1.new_identity("e1"), org2.new_identity("e2")]
    raws, want = [], []
    for i, creator in enumerate(creators):
        if i in FORGED_AT:
            creator = forge(creator)
        rwset = TxRwSet((NsRwSet("cc", writes=(KVWrite(f"k{i}", b"v"),)),))
        env = build.endorser_tx("ch", "cc", "1.0", rwset, creator, endorsers)
        if i in TAMPERED_AT:
            env = tamper(env, creator)
        raws.append(env.serialize())
        want.append(ValidationCode.BAD_CREATOR_SIGNATURE
                    if i in REVOKED_AT + FORGED_AT
                    else ValidationCode.ENDORSEMENT_POLICY_FAILURE
                    if i in TAMPERED_AT else ValidationCode.VALID)
    return raws, [int(c) for c in want], msps, policies


def tamper(env, creator):
    """The envelope signed again over a transaction whose second
    endorsement has one signature byte flipped."""
    from fabric_tpu.protocol import Transaction, TransactionAction
    from fabric_tpu.protocol.types import TX_ENDORSER
    from fabric_tpu.utils import serde
    payload = serde.decode(env.payload)
    tx = Transaction.from_dict(payload["data"])
    ta = tx.actions[0]
    ends = list(ta.endorsements)
    ends[1] = Endorsement(ends[1].endorser,
                          ends[1].signature[:-1]
                          + bytes([ends[1].signature[-1] ^ 1]))
    ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
    header = payload["header"]
    return build.signed_envelope(
        TX_ENDORSER, header["channel_header"]["channel_id"],
        Transaction((ta,)).to_dict(), creator,
        nonce=header["signature_header"]["nonce"])


class _NoDigest:
    """Hide `digest` so the validator takes the classic C-walker +
    Python-tail path."""

    def __init__(self, mod):
        self._mod = mod

    def __getattr__(self, name):
        if name == "digest":
            raise AttributeError(name)
        return getattr(self._mod, name)


@pytest.mark.parametrize("tail", ["deep", "classic", "python"])
def test_both_tails_refuse_revoked_and_forged_creators(unseen_block,
                                                       sw_provider,
                                                       monkeypatch, tail):
    if tv._fastcollect is None and tail != "python":
        pytest.skip("native fastcollect unavailable")
    raws, want, msps, policies = unseen_block
    v = TxValidator("ch", msps, sw_provider, policies)
    if tail == "python":
        v.force_python_collect = True
    elif tail == "classic":
        monkeypatch.setattr(tv, "_fastcollect", _NoDigest(tv._fastcollect))

    def note(name, **labels):
        return counted(name, channel="ch", **labels)

    before = {k: note("validator_creators_total", seen=k)
              for k in ("first", "again")}
    refused0 = {r: note("validator_creator_rejected_total", reason=r)
                for r in ("revoked", "untrusted")}
    spans = []
    monkeypatch.setattr(
        tracing.tracer, "record_span",
        lambda name, t0, t1, attributes=None, parent=None:
        spans.append((name, t1 - t0, attributes)))
    block = Block(BlockHeader(3, b"p", b"d"), list(raws), BlockMetadata())
    state = v.validate_begin(block)
    assert bool(state.get("deep")) == (tail == "deep")
    got = v.validate_finish(state).flags.codes()
    assert [int(c) for c in got] == want
    assert note("validator_creators_total", seen="first") \
        - before["first"] == N_TX
    assert note("validator_creators_total", seen="again") \
        == before["again"]
    assert note("validator_creator_rejected_total", reason="revoked") \
        - refused0["revoked"] == len(REVOKED_AT)
    assert note("validator_creator_rejected_total", reason="untrusted") \
        - refused0["untrusted"] == len(FORGED_AT)
    (name, seconds, attrs), = [s for s in spans
                               if s[0] == "validator.identities"]
    assert attrs == {"block": 3, "unique_creators": N_TX,
                     "unique_endorsers": 2,
                     "rejected": len(REVOKED_AT) + len(FORGED_AT)}
    collect = next(s for s in spans if s[0] == "validator.collect")
    assert 0 < seconds <= collect[1]


def test_creators_a_block_repeats_are_counted_again(sw_provider):
    """64 clients' worth: ten transactions of two creators are two
    first sights and eight repeats, on the tail the node takes."""
    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    endorsers = [org1.new_identity("e1"), org2.new_identity("e2")]
    clients = [org1.new_identity("a"), org2.new_identity("b")]
    raws = [build.endorser_tx(
        "chr", "cc", "1.0",
        TxRwSet((NsRwSet("cc", writes=(KVWrite(f"k{i}", b"v"),)),)),
        clients[i % 2], endorsers).serialize() for i in range(10)]
    v = TxValidator("chr", msps, sw_provider, policies)
    before = {k: counted("validator_creators_total", channel="chr", seen=k)
              for k in ("first", "again")}
    res = v.validate(Block(BlockHeader(1, b"p", b"d"), raws, BlockMetadata()))
    assert res.flags.valid_count() == 10
    after = {k: counted("validator_creators_total", channel="chr", seen=k)
             for k in ("first", "again")}
    assert (after["first"] - before["first"],
            after["again"] - before["again"]) == (2, 8)
