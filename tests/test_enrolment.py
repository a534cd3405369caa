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

from fabric_tpu.bccsp import SCHEME_ED25519, SCHEME_P256
from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.bccsp.sw import P256_HALF_N, SigningKey, SoftwareProvider
from fabric_tpu.committer import PolicyRegistry, TxValidator
from fabric_tpu.committer import txvalidator as tv
from fabric_tpu.config import Bundle, ChannelConfig
from fabric_tpu.crypto import decode_dss_signature, ec, hashes, x509
from fabric_tpu.msp import CachedMSP, Principal
from fabric_tpu.msp.ca import CA, DevOrg
from fabric_tpu.msp.cache import CACHE_SIZE
from fabric_tpu.msp.identity import SigningIdentity
from fabric_tpu.msp.msp import MSP, MSPConfig, MSPValidationError
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


def forge(victim: SigningIdentity, serial=None) -> SigningIdentity:
    """An identity under `victim`'s subject and its CA's issuer name,
    with a key of the forger's own, signed by a key that is not the
    CA's (under `serial`, where the forger copies one)."""
    rogue = ec.generate_private_key(ec.SECP256R1())
    key = ec.generate_private_key(ec.SECP256R1())
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(victim.cert.subject)
            .issuer_name(victim.cert.issuer)
            .public_key(key.public_key())
            .serial_number(serial or x509.random_serial_number())
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


def tamper(env, creator, which=1):
    """The envelope signed again over a transaction whose second
    endorsement (or the one at `which`) has one signature byte
    flipped."""
    from fabric_tpu.protocol import Transaction, TransactionAction
    from fabric_tpu.protocol.types import TX_ENDORSER
    from fabric_tpu.utils import serde
    payload = serde.decode(env.payload)
    tx = Transaction.from_dict(payload["data"])
    ta = tx.actions[0]
    ends = list(ta.endorsements)
    ends[which] = Endorsement(ends[which].endorser,
                              ends[which].signature[:-1]
                              + bytes([ends[which].signature[-1] ^ 1]))
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
        lambda name, t0, t1, attributes=None, parent=None,
        context=None:
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


# -- a CA's signature on the device: the deep tail's deferred links ---------------

class RowsSW(SoftwareProvider):
    """The software provider with what the deep tail reads off a device
    provider: the count at which a key earns the rows lane.  It keeps
    every batch it was handed."""
    fast_key_threshold = 64

    def __init__(self):
        super().__init__()
        self.batches = []

    def batch_verify_async(self, items):
        self.batches.append(list(items))
        return super().batch_verify_async(items)


PER_CA = 200
BIG = {"revoked": (3, 17, 40, 41, 398), "expired": (8, 9),
       "forged": (5, 30, 391), "tampered": (19, 29, 41, 300)}


def high_s(ident) -> bool:
    return decode_dss_signature(ident.cert.signature)[1] > P256_HALF_N


@pytest.fixture(scope="module")
def big_block(sw_provider):
    """(raws, expected codes, fresh MSPs, policies, creators): 400
    transactions, a creator each, 200 under each of two CAs; five
    creators revoked by their org's CRL, two past their validity period,
    three forged, four endorsements tampered (one on a revoked
    creator's transaction), and about half the certificates signed with
    s > n/2, as OpenSSL leaves them."""
    orgs = [DevOrg("Org1"), DevOrg("Org2")]
    past = (datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(minutes=1))
    creators = [orgs[i % 2].new_identity(
        f"user{i}", not_after=past if i in BIG["expired"] else None)
        for i in range(2 * PER_CA)]
    crls = [org.issuer.crl([creators[i].cert for i in BIG["revoked"]
                            if i % 2 == k]) for k, org in enumerate(orgs)]

    def msps():
        return {org.mspid: CachedMSP(org.msp(crls_pem=[crl]))
                for org, crl in zip(orgs, crls)}

    # one endorsement a transaction: on the CPU backend a program takes
    # seconds, and 400 signatures fit the smallest rows grid
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("OR('Org1.member')"))
    endorsers = [orgs[0].new_identity("e1")]
    refused = BIG["revoked"] + BIG["expired"] + BIG["forged"]
    raws, want = [], []
    for i, creator in enumerate(creators):
        if i in BIG["forged"]:
            creator = forge(creator)
        rwset = TxRwSet((NsRwSet("cc", writes=(KVWrite(f"k{i}", b"v"),)),))
        env = build.endorser_tx("big", "cc", "1.0", rwset, creator, endorsers)
        if i in BIG["tampered"]:
            env = tamper(env, creator, which=0)
        raws.append(env.serialize())
        want.append(int(ValidationCode.BAD_CREATOR_SIGNATURE if i in refused
                        else ValidationCode.ENDORSEMENT_POLICY_FAILURE
                        if i in BIG["tampered"] else ValidationCode.VALID))
    assert 100 < sum(high_s(c) for c in creators) < 300
    return raws, want, msps, policies, creators


def run_tail(tail, provider, big_block, monkeypatch):
    """-> (codes, creators refused by reason, leaf links by where,
    chains validated, CA signatures the identities span says were sent)."""
    raws, _want, msps, policies, _creators = big_block
    v = TxValidator("big", msps(), provider, policies)
    if tail == "python":
        v.force_python_collect = True
    elif tail == "classic":
        monkeypatch.setattr(tv, "_fastcollect", _NoDigest(tv._fastcollect))
    reasons = ("revoked", "untrusted", "expired", "undecodable")
    refused0 = {r: counted("validator_creator_rejected_total",
                           channel="big", reason=r) for r in reasons}
    links0 = {w: counted("msp_chain_signatures_total", where=w)
              for w in ("device", "host")}
    seen0 = counted("msp_validate_seconds")
    spans = {}
    monkeypatch.setattr(
        tracing.tracer, "record_span",
        lambda name, t0, t1, attributes=None, parent=None,
        context=None:
        spans.__setitem__(name, attributes))
    state = v.validate_begin(
        Block(BlockHeader(7, b"p", b"d"), list(raws), BlockMetadata()))
    assert bool(state.get("deep")) == tail.startswith("deep")
    codes = [int(c) for c in v.validate_finish(state).flags.codes()]
    monkeypatch.undo()
    sent = spans["validator.identities"].get("deferred", 0)
    assert (sent > 0) == (tail in ("deep-device", "deep-rows"))
    assert ("settle_ms" in spans["validator.dispatch_wait"]) == (sent > 0)
    assert spans["validator.identities"]["rejected"] == sum(
        len(BIG[k]) for k in ("revoked", "expired", "forged"))
    refused = {r: counted("validator_creator_rejected_total", channel="big",
                          reason=r) - refused0[r] for r in reasons}
    links = {w: counted("msp_chain_signatures_total", where=w) - links0[w]
             for w in links0}
    return (codes, refused, links, counted("msp_validate_seconds") - seen0,
            sent)


@pytest.mark.parametrize("tail", ["deep-device", "deep-rows", "deep",
                                  "classic", "python"])
def test_a_block_of_unseen_creators_reads_the_same_on_every_tail(
        big_block, sw_provider, monkeypatch, tail):
    """Flag for flag and refusal for refusal: the deep tail with the
    device provider (CPU backend) and with `RowsSW`, both deferring the
    CA's signatures; the deep tail on the software provider, the
    classic and the Python tail, every chain on the host."""
    if tv._fastcollect is None and tail != "python":
        pytest.skip("native fastcollect unavailable")
    want = big_block[1]
    if tail == "deep-device":
        from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
        provider = JaxTpuProvider()
    else:
        provider = RowsSW() if tail == "deep-rows" else sw_provider
    codes, refused, links, observed, sent = run_tail(
        tail, provider, big_block, monkeypatch)
    assert codes == want
    assert refused == {"revoked": len(BIG["revoked"]),
                       "untrusted": len(BIG["forged"]),
                       "expired": len(BIG["expired"]), "undecodable": 0}
    # one observation an identity on both branches: 400 creators and
    # the endorser the policy's principal validates
    assert observed == 2 * PER_CA + 1
    checked_here = len(BIG["revoked"]) + len(BIG["expired"])
    if tail in ("deep-device", "deep-rows"):
        # the host checks decided first and sent nothing for those
        assert links == {"device": 2 * PER_CA - checked_here, "host": 1}
        assert sent == 2 * PER_CA - checked_here
    else:
        assert links == {"device": 0, "host": 2 * PER_CA + 1}
    if tail == "deep-device":
        # two programs of the block's own and one for the certificates
        assert provider.stats["dispatches"] == 3
        assert provider.stats["fallbacks"] == 0
    if tail == "deep-rows":
        certs, = [b for b in provider.batches
                  if len(b) == 2 * PER_CA - checked_here]
        assert all(decode_dss_signature(it.signature)[1] <= P256_HALF_N
                   for it in certs)
        assert len({it.pubkey for it in certs}) == 2


def hot_block(endorser, creators, channel="hot"):
    raws = [build.endorser_tx(
        channel, "cc", "1.0",
        TxRwSet((NsRwSet("cc", writes=(KVWrite(f"k{i}", b"v"),)),)),
        c, [endorser]).serialize() for i, c in enumerate(creators)]
    return Block(BlockHeader(1, b"p", b"d"), raws, BlockMetadata())


def test_settlement_fills_the_cache_as_validate_does(sw_provider):
    """150 unseen creators of one CA, one forged, through the deferred
    branch: the validation cache holds what 150 `validate` calls in the
    block's order leave — the last 100, the forged one a cached
    `untrusted` — and the same creators in the next block are hits that
    send nothing."""
    if tv._fastcollect is None:
        pytest.skip("native fastcollect unavailable")
    org = DevOrg("Org1")
    creators = [org.new_identity(f"u{i}") for i in range(150)]
    creators[120] = forge(creators[120])
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("OR('Org1.member')"))
    cmsp, twin = CachedMSP(org.msp()), CachedMSP(org.msp())
    provider = RowsSW()
    endorser = org.new_identity("e")
    v = TxValidator("hot", {"Org1": cmsp}, provider, policies)
    res = v.validate(hot_block(endorser, creators))
    assert res.flags.valid_count() == 149
    assert [len(b) for b in provider.batches] == [150, 300]
    for c in creators:
        try:
            twin.validate(twin.deserialize_identity(c.serialize()))
        except MSPValidationError:
            pass

    def held(m):
        return [(k.cert.serial_number, getattr(e, "reason", e))
                for k, e in m._valid._d.items()]

    assert held(cmsp) == held(twin) and len(held(cmsp)) == CACHE_SIZE
    assert held(cmsp)[0][0] == creators[50].cert.serial_number
    assert (creators[120].cert.serial_number, "untrusted") in held(cmsp)
    # the next block: the cached hundred again
    hits0 = counted("msp_cache_total", msp="Org1", op="validate",
                    result="hit")
    seen0 = counted("msp_validate_seconds", msp="Org1")
    del provider.batches[:]
    res = v.validate(hot_block(endorser, creators[50:]))
    assert res.flags.valid_count() == 99
    assert counted("msp_cache_total", msp="Org1", op="validate",
                   result="hit") - hits0 == 100
    assert counted("msp_validate_seconds", msp="Org1") == seen0
    # one dispatch, the block's own: 99 sound creators x 2 signatures
    assert [len(b) for b in provider.batches] == [198]
    assert held(cmsp) == held(twin)


def test_a_refused_identity_keeps_nothing_of_its_block_alive(sw_provider):
    """An error `validate_many` stores carries no traceback: that would
    hold the call's frame, and through it every identity of the block,
    in a cycle only a whole-heap pass frees — and a committing peer
    freezes what a block leaves (`utils/heap.py`)."""
    import gc
    import weakref
    org = DevOrg("Org1")
    members = [org.new_identity(f"u{i}") for i in range(2 * CACHE_SIZE + 20)]
    half = len(members) // 2
    crl = org.issuer.crl([members[7].cert, members[half + 7].cert])
    cmsp = CachedMSP(org.msp(crls_pem=[crl]))

    def block(some):
        idents = [cmsp.inner.deserialize_identity(m.serialize())
                  for m in some]
        errors, deferred = cmsp.validate_many(idents, 64)
        assert [e.reason for e in errors if e is not None] == ["revoked"]
        assert all(e.__traceback__ is None for e in errors if e is not None)
        assert deferred.settle([True] * len(deferred.items)) == []
        return weakref.ref(idents[0])

    gc.collect()
    gc.disable()
    try:
        first = block(members[:half])
        block(members[half:])       # evicts the first block's entries
        assert first() is None
    finally:
        gc.enable()


def two_keyed(name: str):
    """An org whose CA rolled its key over: two trusted roots of one
    subject -> (MSP config, the newer CA)."""
    old, new = CA(name), CA(name)
    assert old.cert.subject == new.cert.subject
    return MSPConfig(mspid=name, root_certs_pem=[old.cert_pem(),
                                                 new.cert_pem()]), new


def sha384_identity(org: DevOrg, name: str) -> SigningIdentity:
    key = ec.generate_private_key(ec.SECP256R1())
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(x509.Name([x509.NameAttribute(
                x509.oid.NameOID.COMMON_NAME, name)]))
            .issuer_name(org.issuer.cert.subject)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=30))
            .sign(org.issuer._key, hashes.SHA384()))
    return SigningIdentity(org.mspid, cert, SigningKey(SCHEME_P256, key))


@pytest.mark.parametrize("case", ["few", "two_issuers", "ed25519_ca",
                                  "sha384_link", "blind"])
def test_links_that_stay_on_the_host(sw_provider, case):
    """Under the threshold, two trusted candidates of the issuer's name,
    a link that is not P-256 over SHA-256, an MSP whose `validate` was
    replaced on the instance: `validate_many` defers nothing, answers
    what `validate` answers and books the links under `host`."""
    n = 70
    if case == "two_issuers":
        config, ca = two_keyed("Rolled")
        msp = MSP(config)
        idents = [SigningIdentity("Rolled", *_issue(ca, f"u{i}"))
                  for i in range(n)]
    elif case == "ed25519_ca":
        org = DevOrg("EdCa", scheme=SCHEME_ED25519)
        msp, idents = org.msp(), [org.new_identity(f"u{i}") for i in range(n)]
    else:
        org = DevOrg("Plain" + case)
        msp = org.msp()
        if case == "sha384_link":
            idents = [sha384_identity(org, f"u{i}") for i in range(n)]
        else:
            idents = [org.new_identity(f"u{i}")
                      for i in range(10 if case == "few" else n)]
    if case == "blind":
        msp.validate = lambda ident, at_time=None: None
        idents[3] = forge(idents[3])
    cmsp = CachedMSP(msp)
    host0 = counted("msp_chain_signatures_total", msp=msp.mspid, where="host")
    errors, deferred = cmsp.validate_many(idents, 64)
    assert deferred is None and errors == [None] * len(idents)
    assert counted("msp_chain_signatures_total", msp=msp.mspid,
                   where="device") == 0
    assert counted("msp_chain_signatures_total", msp=msp.mspid,
                   where="host") - host0 == (0 if case == "blind"
                                             else len(idents))
    assert all(cmsp.is_valid(i) for i in idents)


def _issue(ca: CA, name: str):
    cert, key = ca.issue(name)
    return cert, SigningKey(SCHEME_P256, key)


def test_a_lite_certificate_stays_on_the_host():
    """`crypto/lite_x509` certificates state no signature algorithm and
    give no to-be-signed bytes: never eligible."""
    import types
    from fabric_tpu.crypto import lite_ec, lite_hashes, lite_x509
    from fabric_tpu.msp import msp as mspmod
    key = lite_ec.generate_private_key(lite_ec.SECP256R1())
    org = DevOrg("LiteHost")
    now = datetime.datetime.now(datetime.timezone.utc)
    name = lite_x509.Name([lite_x509.NameAttribute(
        lite_x509.NameOID.COMMON_NAME, "lite")])
    cert = (lite_x509.CertificateBuilder().subject_name(name)
            .issuer_name(name).public_key(key.public_key())
            .serial_number(7).not_valid_before(now)
            .not_valid_after(now + datetime.timedelta(days=1))
            .sign(key, lite_hashes.SHA256()))
    assert mspmod._link_algorithm(cert) is None
    sound = org.new_identity("sound")
    msp = org.msp()
    assert msp.deferrable_under(sound) is not None
    lite = types.SimpleNamespace(cert=cert, issuer_der=sound.issuer_der)
    assert msp.deferrable_under(lite) is None


def test_the_host_decides_first_on_the_deferred_branch(sw_provider):
    """The one order that differs, pinned: `validate` checks the CA's
    signature before the CRL, so a certificate both forged and listed by
    serial is `untrusted` there; `validate_deferred` runs the host
    checks first, refuses it as `revoked` and hands nothing back.  The
    flag is BAD_CREATOR_SIGNATURE either way."""
    org = DevOrg("Org1")
    victim = org.new_identity("victim")
    msp = org.msp(crls_pem=[org.issuer.crl([victim.cert])])
    both = forge(victim, serial=victim.cert.serial_number)
    with pytest.raises(MSPValidationError) as on_host:
        msp.validate(both)
    assert on_host.value.reason == "untrusted"
    with pytest.raises(MSPValidationError) as deferred:
        msp.validate_deferred(both)
    assert deferred.value.reason == "revoked"
    # a forged one alone comes back as a link only a provider refuses
    forged = forge(victim)
    link = msp.validate_deferred(forged)
    assert not sw_provider.verify(link.item)
    err, = msp.settle_many([forged], [link], [False])
    assert err.reason == "untrusted"
    sound = org.new_identity("sound")
    link = msp.validate_deferred(sound)
    assert sw_provider.verify(link.item)
    assert msp.settle_many([sound], [link], [True]) == [None]
