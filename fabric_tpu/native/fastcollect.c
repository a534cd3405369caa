/* Fast block collection — C implementation of txvalidator pass 1.
 *
 * The verify-then-gate validator (fabric_tpu/committer/txvalidator.py)
 * spends pass 1 walking every envelope of a block: decode, structural
 * checks, txid derivation, and collection of the signed byte spans the
 * device will verify.  The reference parallelizes the equivalent work
 * across goroutines (core/committer/txvalidator/v20/validator.go:194-209);
 * this host has ONE core, so the same win comes from doing the walk in C
 * over the canonical FTLV encoding (fabric_tpu/utils/serde.py) without
 * materializing any intermediate Python objects.
 *
 * Exported:  collect(envs: sequence[bytes], channel_id: str) -> list
 *            digest(envs, channel_id, carry, oracle) -> digested pass 1
 *            assemble(works, ...) -> per-tx gate plans + the signature
 *                                    table (SigTable: P-256 items as
 *                                    flat buffers, no object an item)
 *            pack_items(items, ...) -> the same table from VerifyItems
 *            gate(plans, verdict, codes, ...) -> fold verdicts into flags
 *
 * collect() is the span-splicing walker shared by the classic consumer
 * tail (txvalidator._collect_tx_fast: the blocks a key-level validation
 * parameter could touch); digest/
 * assemble/gate are the fully-native tail: txid dedup against a C-side
 * seen-set (plus the pipelined carry window and the ledger oracle),
 * creator/endorser memo SLOT assignment, the block's unique signatures
 * written in dispatch order into one table of flat buffers, and a
 * verdict-bitmap gate that never runs a per-tx Python loop.  The
 * no-compiler mirror for ALL of it is
 * committer/collect_py.py + the Python tail/gate in txvalidator.py —
 * the two paths must produce bit-identical TxFlags (state-fork
 * invariant, tested differentially in tests/test_committer.py).
 *
 * Per envelope the collect() result element is either
 *   int code — an early validation failure:
 *     1=NIL_ENVELOPE 2=BAD_PAYLOAD 3=TARGET_CHAIN_NOT_FOUND
 *     4=BAD_PROPOSAL_TXID 5=UNKNOWN_TX_TYPE 6=NIL_TXACTION
 * or the tuple
 *   (txtype, txid, creator, payload, payload_digest, signature, actions)
 *     txtype: 0 = config, 1 = endorser transaction
 *     txid:   str (hex, already checked == sha256(nonce||creator))
 *     payload_digest: sha256(payload) — the P-256 creator item payload
 *     actions: None for config txs, else a list of
 *       (chaincode_id, endorsed, endorsements, ns_writes, meta_writes)
 *         endorsed:     the exact bytes every endorsement signs
 *                       (serde {action, proposal_hash} re-spliced from
 *                        the original encoding by span copy)
 *         endorsements: [(endorser, sig, sha256(endorsed||endorser)), ...]
 *         ns_writes:    [(namespace, (written keys...), (deleted keys...)),
 *                        ...]  (non-meta; the deleted are among the
 *                        written: a key's validation parameter goes
 *                        with the key, committer/sbe.py)
 *         meta_writes:  [(base_ns, key, value|None), ...]      ("#meta")
 *
 * SHA-256 uses the x86 SHA extensions when the CPU has them (this host
 * does) with a portable scalar fallback — hashing payload spans is the
 * bulk of the byte traffic here.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <structmember.h>
#include <stddef.h>
#include <stdint.h>
#include <string.h>

#if defined(__x86_64__) || defined(_M_X64)
#include <immintrin.h>
#include <cpuid.h>
#define HAVE_X86 1
#endif

/* ------------------------------------------------------------------ */
/* SHA-256                                                             */

typedef struct {
    uint32_t h[8];
    uint64_t nbytes;
    uint8_t buf[64];
    size_t buflen;
} sha256_t;

static const uint32_t K256[64] = {
    0x428a2f98,0x71374491,0xb5c0fbcf,0xe9b5dba5,0x3956c25b,0x59f111f1,
    0x923f82a4,0xab1c5ed5,0xd807aa98,0x12835b01,0x243185be,0x550c7dc3,
    0x72be5d74,0x80deb1fe,0x9bdc06a7,0xc19bf174,0xe49b69c1,0xefbe4786,
    0x0fc19dc6,0x240ca1cc,0x2de92c6f,0x4a7484aa,0x5cb0a9dc,0x76f988da,
    0x983e5152,0xa831c66d,0xb00327c8,0xbf597fc7,0xc6e00bf3,0xd5a79147,
    0x06ca6351,0x14292967,0x27b70a85,0x2e1b2138,0x4d2c6dfc,0x53380d13,
    0x650a7354,0x766a0abb,0x81c2c92e,0x92722c85,0xa2bfe8a1,0xa81a664b,
    0xc24b8b70,0xc76c51a3,0xd192e819,0xd6990624,0xf40e3585,0x106aa070,
    0x19a4c116,0x1e376c08,0x2748774c,0x34b0bcb5,0x391c0cb3,0x4ed8aa4a,
    0x5b9cca4f,0x682e6ff3,0x748f82ee,0x78a5636f,0x84c87814,0x8cc70208,
    0x90befffa,0xa4506ceb,0xbef9a3f7,0xc67178f2};

#define ROR(x, n) (((x) >> (n)) | ((x) << (32 - (n))))

static void sha256_block_scalar(uint32_t h[8], const uint8_t *p, size_t nblk)
{
    uint32_t w[64];
    while (nblk--) {
        for (int i = 0; i < 16; i++)
            w[i] = ((uint32_t)p[4*i] << 24) | ((uint32_t)p[4*i+1] << 16)
                 | ((uint32_t)p[4*i+2] << 8) | p[4*i+3];
        for (int i = 16; i < 64; i++) {
            uint32_t s0 = ROR(w[i-15], 7) ^ ROR(w[i-15], 18) ^ (w[i-15] >> 3);
            uint32_t s1 = ROR(w[i-2], 17) ^ ROR(w[i-2], 19) ^ (w[i-2] >> 10);
            w[i] = w[i-16] + s0 + w[i-7] + s1;
        }
        uint32_t a=h[0],b=h[1],c=h[2],d=h[3],e=h[4],f=h[5],g=h[6],hh=h[7];
        for (int i = 0; i < 64; i++) {
            uint32_t S1 = ROR(e,6) ^ ROR(e,11) ^ ROR(e,25);
            uint32_t ch = (e & f) ^ (~e & g);
            uint32_t t1 = hh + S1 + ch + K256[i] + w[i];
            uint32_t S0 = ROR(a,2) ^ ROR(a,13) ^ ROR(a,22);
            uint32_t mj = (a & b) ^ (a & c) ^ (b & c);
            uint32_t t2 = S0 + mj;
            hh=g; g=f; f=e; e=d+t1; d=c; c=b; b=a; a=t1+t2;
        }
        h[0]+=a; h[1]+=b; h[2]+=c; h[3]+=d;
        h[4]+=e; h[5]+=f; h[6]+=g; h[7]+=hh;
        p += 64;
    }
}

#ifdef HAVE_X86
__attribute__((target("sha,sse4.1")))
static void sha256_block_shani(uint32_t h[8], const uint8_t *p, size_t nblk)
{
    const __m128i MASK = _mm_set_epi64x(0x0c0d0e0f08090a0bULL,
                                        0x0405060700010203ULL);
    /* load state: h = {a,b,c,d,e,f,g,h} -> ABEF/CDGH lanes */
    __m128i tmp = _mm_loadu_si128((const __m128i *)&h[0]);   /* d c b a */
    __m128i st1 = _mm_loadu_si128((const __m128i *)&h[4]);   /* h g f e */
    tmp = _mm_shuffle_epi32(tmp, 0xB1);                      /* c d a b */
    st1 = _mm_shuffle_epi32(st1, 0x1B);                      /* e f g h */
    __m128i state0 = _mm_alignr_epi8(tmp, st1, 8);           /* abef */
    __m128i state1 = _mm_blend_epi16(st1, tmp, 0xF0);        /* cdgh */

    while (nblk--) {
        __m128i s0 = state0, s1 = state1, msg, m0, m1, m2, m3;
        m0 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(p +  0)), MASK);
        m1 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(p + 16)), MASK);
        m2 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(p + 32)), MASK);
        m3 = _mm_shuffle_epi8(_mm_loadu_si128((const __m128i*)(p + 48)), MASK);

#define RND4(mcur, mprev2, kidx)                                         \
        msg = _mm_add_epi32(mcur, _mm_loadu_si128(                       \
                  (const __m128i *)&K256[kidx]));                        \
        state1 = _mm_sha256rnds2_epu32(state1, state0, msg);             \
        msg = _mm_shuffle_epi32(msg, 0x0E);                              \
        state0 = _mm_sha256rnds2_epu32(state0, state1, msg);
#define SCHED(mnext, m_3, m_2, m_1)                                      \
        mnext = _mm_sha256msg1_epu32(mnext, m_3);                        \
        mnext = _mm_add_epi32(mnext, _mm_alignr_epi8(m_1, m_2, 4));      \
        mnext = _mm_sha256msg2_epu32(mnext, m_1);

        RND4(m0, m0, 0)
        RND4(m1, m1, 4)
        RND4(m2, m2, 8)
        RND4(m3, m3, 12)
        for (int r = 16; r < 64; r += 16) {
            SCHED(m0, m1, m2, m3) RND4(m0, m0, r)
            SCHED(m1, m2, m3, m0) RND4(m1, m1, r + 4)
            SCHED(m2, m3, m0, m1) RND4(m2, m2, r + 8)
            SCHED(m3, m0, m1, m2) RND4(m3, m3, r + 12)
        }
#undef RND4
#undef SCHED
        state0 = _mm_add_epi32(state0, s0);
        state1 = _mm_add_epi32(state1, s1);
        p += 64;
    }
    tmp = _mm_shuffle_epi32(state0, 0x1B);                   /* feba */
    st1 = _mm_shuffle_epi32(state1, 0xB1);                   /* dchg */
    state0 = _mm_blend_epi16(tmp, st1, 0xF0);                /* dcba */
    state1 = _mm_alignr_epi8(st1, tmp, 8);                   /* hgfe */
    _mm_storeu_si128((__m128i *)&h[0], state0);
    _mm_storeu_si128((__m128i *)&h[4], state1);
}
#endif

static void (*sha256_block)(uint32_t[8], const uint8_t *, size_t)
    = sha256_block_scalar;

static void sha256_init(sha256_t *s)
{
    static const uint32_t iv[8] = {
        0x6a09e667,0xbb67ae85,0x3c6ef372,0xa54ff53a,
        0x510e527f,0x9b05688c,0x1f83d9ab,0x5be0cd19};
    memcpy(s->h, iv, sizeof iv);
    s->nbytes = 0;
    s->buflen = 0;
}

static void sha256_update(sha256_t *s, const uint8_t *p, size_t n)
{
    s->nbytes += n;
    if (s->buflen) {
        size_t take = 64 - s->buflen;
        if (take > n) take = n;
        memcpy(s->buf + s->buflen, p, take);
        s->buflen += take;
        p += take;
        n -= take;
        if (s->buflen == 64) {
            sha256_block(s->h, s->buf, 1);
            s->buflen = 0;
        }
    }
    size_t nblk = n / 64;
    if (nblk) {
        sha256_block(s->h, p, nblk);
        p += nblk * 64;
        n -= nblk * 64;
    }
    if (n) {
        memcpy(s->buf, p, n);
        s->buflen = n;
    }
}

static void sha256_final(sha256_t *s, uint8_t out[32])
{
    uint64_t bits = s->nbytes * 8;
    uint8_t pad[72];
    size_t padlen = (s->buflen < 56) ? 56 - s->buflen : 120 - s->buflen;
    memset(pad, 0, sizeof pad);
    pad[0] = 0x80;
    for (int i = 0; i < 8; i++)
        pad[padlen + i] = (uint8_t)(bits >> (56 - 8 * i));
    sha256_update(s, pad, padlen + 8);
    for (int i = 0; i < 8; i++) {
        out[4*i]   = (uint8_t)(s->h[i] >> 24);
        out[4*i+1] = (uint8_t)(s->h[i] >> 16);
        out[4*i+2] = (uint8_t)(s->h[i] >> 8);
        out[4*i+3] = (uint8_t)(s->h[i]);
    }
}

static void sha256_oneshot(const uint8_t *p, size_t n, uint8_t out[32])
{
    sha256_t s;
    sha256_init(&s);
    sha256_update(&s, p, n);
    sha256_final(&s, out);
}

/* ------------------------------------------------------------------ */
/* FTLV walker (format: fabric_tpu/utils/serde.py)                     */

typedef struct {
    const uint8_t *p;
    const uint8_t *end;
} cur_t;

static int rd_u32(cur_t *c, uint32_t *out)
{
    if (c->end - c->p < 4) return -1;
    *out = ((uint32_t)c->p[0] << 24) | ((uint32_t)c->p[1] << 16)
         | ((uint32_t)c->p[2] << 8) | c->p[3];
    c->p += 4;
    return 0;
}

/* Nesting cap: legitimate framework messages are a few levels deep; an
 * attacker-crafted envelope of ~150k nested lists would otherwise blow
 * the C stack (the Python fallback raises RecursionError -> BAD_PAYLOAD;
 * the C walker must degrade identically, never segfault). */
#define MAX_DEPTH 64

/* skip one encoded value; returns 0 ok / -1 malformed-or-too-deep */
static int skip_value_d(cur_t *c, int depth)
{
    if (depth > MAX_DEPTH) return -1;
    if (c->p >= c->end) return -1;
    uint8_t tag = *c->p++;
    uint32_t n;
    switch (tag) {
    case 'N': case 'T': case 'F':
        return 0;
    case 'I':
        if (c->end - c->p < 8) return -1;
        c->p += 8;
        return 0;
    case 'V': case 'B': case 'S':
        if (rd_u32(c, &n) < 0 || (uint32_t)(c->end - c->p) < n) return -1;
        c->p += n;
        return 0;
    case 'L':
        if (rd_u32(c, &n) < 0) return -1;
        while (n--)
            if (skip_value_d(c, depth + 1) < 0) return -1;
        return 0;
    case 'D':
        if (rd_u32(c, &n) < 0) return -1;
        while (n--) {
            uint32_t kn;
            if (rd_u32(c, &kn) < 0
                || (uint32_t)(c->end - c->p) < kn) return -1;
            c->p += kn;
            if (skip_value_d(c, depth + 1) < 0) return -1;
        }
        return 0;
    default:
        return -1;
    }
}

static int skip_value(cur_t *c)
{
    return skip_value_d(c, 0);
}

/* ------------------------------------------------------------------ */
/* Canonical-form validation.
 *
 * The walker below splices SIGNED byte spans straight out of the
 * original encoding (the endorsed bytes every endorsement signature
 * covers), while the no-compiler Python path re-encodes the decoded
 * value through serde.encode.  Splice == re-encode ONLY for canonical
 * input, so every envelope is rejected to BAD_PAYLOAD unless it is
 * exactly the canonical encoding serde.encode would produce: strictly
 * increasing (hence unique) dict keys, minimal 'V' ints >= 2^63, valid
 * UTF-8 strings, nesting <= MAX_DEPTH, no trailing bytes.  serde.py and
 * native/ftlv.c enforce the same rules on decode, keeping C-enabled
 * and pure-Python peers on identical validity bitmaps. */

/* strict UTF-8 (CPython decoder semantics: no overlongs, no
 * surrogates, max U+10FFFF) */
static int utf8_ok(const uint8_t *p, uint32_t n)
{
    uint32_t i = 0;
    while (i < n) {
        uint8_t b = p[i];
        if (b < 0x80) { i++; continue; }
        if (b < 0xC2) return 0;              /* continuation / overlong */
        if (b < 0xE0) {                      /* 2-byte */
            if (n - i < 2 || (p[i+1] & 0xC0) != 0x80) return 0;
            i += 2; continue;
        }
        if (b < 0xF0) {                      /* 3-byte */
            if (n - i < 3) return 0;
            uint8_t b1 = p[i+1], b2 = p[i+2];
            if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80) return 0;
            if (b == 0xE0 && b1 < 0xA0) return 0;        /* overlong */
            if (b == 0xED && b1 >= 0xA0) return 0;       /* surrogate */
            i += 3; continue;
        }
        if (b < 0xF5) {                      /* 4-byte */
            if (n - i < 4) return 0;
            uint8_t b1 = p[i+1], b2 = p[i+2], b3 = p[i+3];
            if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80
                || (b3 & 0xC0) != 0x80) return 0;
            if (b == 0xF0 && b1 < 0x90) return 0;        /* overlong */
            if (b == 0xF4 && b1 >= 0x90) return 0;       /* > U+10FFFF */
            i += 4; continue;
        }
        return 0;
    }
    return 1;
}

static int canon_value_d(cur_t *c, int depth)
{
    if (depth > MAX_DEPTH) return -1;
    if (c->p >= c->end) return -1;
    uint8_t tag = *c->p++;
    uint32_t n;
    switch (tag) {
    case 'N': case 'T': case 'F':
        return 0;
    case 'I':
        if (c->end - c->p < 8) return -1;
        c->p += 8;
        return 0;
    case 'V':
        if (rd_u32(c, &n) < 0 || (uint32_t)(c->end - c->p) < n) return -1;
        /* minimal magnitude, >= 2^63 (encoder emits 'I' below that) */
        if (n < 8 || c->p[0] == 0 || (n == 8 && c->p[0] < 0x80))
            return -1;
        c->p += n;
        return 0;
    case 'B':
        if (rd_u32(c, &n) < 0 || (uint32_t)(c->end - c->p) < n) return -1;
        c->p += n;
        return 0;
    case 'S':
        if (rd_u32(c, &n) < 0 || (uint32_t)(c->end - c->p) < n) return -1;
        if (!utf8_ok(c->p, n)) return -1;
        c->p += n;
        return 0;
    case 'L':
        if (rd_u32(c, &n) < 0) return -1;
        while (n--)
            if (canon_value_d(c, depth + 1) < 0) return -1;
        return 0;
    case 'D': {
        if (rd_u32(c, &n) < 0) return -1;
        const uint8_t *prev = NULL;
        uint32_t prev_n = 0;
        while (n--) {
            uint32_t kn;
            const uint8_t *k;
            if (rd_u32(c, &kn) < 0
                || (uint32_t)(c->end - c->p) < kn) return -1;
            k = c->p;
            c->p += kn;
            if (!utf8_ok(k, kn)) return -1;
            if (prev) {
                /* strictly increasing bytewise (UTF-8 order ==
                 * code-point order) — also bans duplicate keys */
                uint32_t m = prev_n < kn ? prev_n : kn;
                int cmp = memcmp(prev, k, m);
                if (cmp > 0 || (cmp == 0 && prev_n >= kn)) return -1;
            }
            prev = k;
            prev_n = kn;
            if (canon_value_d(c, depth + 1) < 0) return -1;
        }
        return 0;
    }
    default:
        return -1;
    }
}

/* exactly one canonical value filling the span */
static int canon_span(const uint8_t *p, size_t n)
{
    cur_t c = {p, p + n};
    if (canon_value_d(&c, 0) < 0) return -1;
    return c.p == c.end ? 0 : -1;
}

/* Enter a dict ('D'): returns entry count or -1. */
static int dict_enter(cur_t *c, uint32_t *count)
{
    if (c->p >= c->end || *c->p != 'D') return -1;
    c->p++;
    return rd_u32(c, count);
}

/* Read the next dict entry's key span; value left at cursor. */
static int dict_key(cur_t *c, const uint8_t **key, uint32_t *klen)
{
    if (rd_u32(c, klen) < 0 || (uint32_t)(c->end - c->p) < *klen) return -1;
    *key = c->p;
    c->p += *klen;
    return 0;
}

static int key_is(const uint8_t *key, uint32_t klen, const char *name)
{
    size_t n = strlen(name);
    return klen == n && memcmp(key, name, n) == 0;
}

/* read a 'B' (bytes) value span */
static int rd_bytes(cur_t *c, const uint8_t **p, uint32_t *n)
{
    if (c->p >= c->end || *c->p != 'B') return -1;
    c->p++;
    if (rd_u32(c, n) < 0 || (uint32_t)(c->end - c->p) < *n) return -1;
    *p = c->p;
    c->p += *n;
    return 0;
}

/* read an 'S' (str) value span */
static int rd_str(cur_t *c, const uint8_t **p, uint32_t *n)
{
    if (c->p >= c->end || *c->p != 'S') return -1;
    c->p++;
    if (rd_u32(c, n) < 0 || (uint32_t)(c->end - c->p) < *n) return -1;
    *p = c->p;
    c->p += *n;
    return 0;
}

/* read a bool; -1 on anything else */
static int rd_bool(cur_t *c, int *val)
{
    if (c->p >= c->end) return -1;
    if (*c->p == 'T') { *val = 1; c->p++; return 0; }
    if (*c->p == 'F') { *val = 0; c->p++; return 0; }
    return -1;
}

/* span of the next value (tag..end), cursor advanced past it */
static int value_span(cur_t *c, const uint8_t **p, size_t *n)
{
    const uint8_t *start = c->p;
    if (skip_value(c) < 0) return -1;
    *p = start;
    *n = (size_t)(c->p - start);
    return 0;
}

/* ------------------------------------------------------------------ */
/* collection                                                          */

#define E_NIL_ENVELOPE 1
#define E_BAD_PAYLOAD 2
#define E_TARGET_CHAIN 3
#define E_BAD_TXID 4
#define E_UNKNOWN_TYPE 5
#define E_NIL_TXACTION 6

static const char HEXD[] = "0123456789abcdef";

/* Parse one ns rwset dict: append written keys / meta writes to the
 * provided lists.  Returns 0 ok / -1 malformed. */
static int do_ns_rwset(cur_t *c, PyObject *ns_writes, PyObject *meta_writes)
{
    uint32_t nent;
    if (dict_enter(c, &nent) < 0) return -1;
    const uint8_t *ns_p = NULL;
    uint32_t ns_n = 0;
    const uint8_t *writes_p = NULL;
    const uint8_t *writes_end = NULL;
    while (nent--) {
        const uint8_t *key; uint32_t klen;
        if (dict_key(c, &key, &klen) < 0) return -1;
        if (key_is(key, klen, "namespace")) {
            if (rd_str(c, &ns_p, &ns_n) < 0) return -1;
        } else if (key_is(key, klen, "writes")) {
            writes_p = c->p;
            if (skip_value(c) < 0) return -1;
            writes_end = c->p;
        } else {
            if (skip_value(c) < 0) return -1;
        }
    }
    if (!ns_p) return -1;
    if (!writes_p) return 0;
    cur_t w = {writes_p, writes_end};
    if (w.p >= w.end || *w.p != 'L') return -1;
    w.p++;
    uint32_t nw;
    if (rd_u32(&w, &nw) < 0) return 0;
    if (nw == 0) return 0;

    /* ">= 5": a namespace that IS exactly "#meta" is meta with base ""
     * (Python endswith + base_namespace slicing semantics, sbe.py) */
    int is_meta = ns_n >= 5 && memcmp(ns_p + ns_n - 5, "#meta", 5) == 0;
    PyObject *ns_str = NULL, *keys_list = NULL, *dels_list = NULL;
    if (is_meta)
        ns_str = PyUnicode_DecodeUTF8((const char *)ns_p, ns_n - 5, NULL);
    else {
        ns_str = PyUnicode_DecodeUTF8((const char *)ns_p, ns_n, NULL);
        keys_list = PyList_New(0);
    }
    if (!ns_str || (!is_meta && !keys_list)) {
        Py_XDECREF(ns_str);
        Py_XDECREF(keys_list);
        return -1;
    }
    int rc = 0;
    while (nw-- && rc == 0) {
        uint32_t nent2;
        if (dict_enter(&w, &nent2) < 0) { rc = -1; break; }
        const uint8_t *k_p = NULL, *v_p = NULL;
        uint32_t k_n = 0, v_n = 0;
        int is_delete = 0;
        while (nent2--) {
            const uint8_t *key; uint32_t klen;
            if (dict_key(&w, &key, &klen) < 0) { rc = -1; break; }
            if (key_is(key, klen, "key")) {
                if (rd_str(&w, &k_p, &k_n) < 0) { rc = -1; break; }
            } else if (key_is(key, klen, "is_delete")) {
                if (rd_bool(&w, &is_delete) < 0) { rc = -1; break; }
            } else if (is_meta && key_is(key, klen, "value")) {
                if (rd_bytes(&w, &v_p, &v_n) < 0) { rc = -1; break; }
            } else {
                if (skip_value(&w) < 0) { rc = -1; break; }
            }
        }
        if (rc < 0 || !k_p) { rc = -1; break; }
        PyObject *kstr = PyUnicode_DecodeUTF8((const char *)k_p, k_n, NULL);
        if (!kstr) { rc = -1; break; }
        if (is_meta) {
            PyObject *val;
            if (is_delete) {
                val = Py_None;
                Py_INCREF(val);
            } else {
                val = PyBytes_FromStringAndSize((const char *)v_p, v_n);
                if (!val) { Py_DECREF(kstr); rc = -1; break; }
            }
            PyObject *tup = PyTuple_New(3);
            if (!tup) {
                Py_DECREF(kstr); Py_DECREF(val); rc = -1; break;
            }
            Py_INCREF(ns_str);
            PyTuple_SET_ITEM(tup, 0, ns_str);
            PyTuple_SET_ITEM(tup, 1, kstr);
            PyTuple_SET_ITEM(tup, 2, val);
            rc = PyList_Append(meta_writes, tup);
            Py_DECREF(tup);
        } else {
            rc = PyList_Append(keys_list, kstr);
            if (rc == 0 && is_delete) {
                if (!dels_list) dels_list = PyList_New(0);
                rc = dels_list ? PyList_Append(dels_list, kstr) : -1;
            }
            Py_DECREF(kstr);
        }
    }
    if (rc == 0 && !is_meta) {
        PyObject *keys_tup = PyList_AsTuple(keys_list);
        /* no delete (nearly every rw-set): the shared empty tuple */
        PyObject *dels_tup = dels_list ? PyList_AsTuple(dels_list)
                                       : PyTuple_New(0);
        PyObject *triple = (keys_tup && dels_tup) ? PyTuple_New(3) : NULL;
        if (!triple) {
            Py_XDECREF(keys_tup);
            Py_XDECREF(dels_tup);
            rc = -1;
        } else {
            Py_INCREF(ns_str);
            PyTuple_SET_ITEM(triple, 0, ns_str);
            PyTuple_SET_ITEM(triple, 1, keys_tup);
            PyTuple_SET_ITEM(triple, 2, dels_tup);
            rc = PyList_Append(ns_writes, triple);
            Py_DECREF(triple);
        }
    }
    Py_DECREF(ns_str);
    Py_XDECREF(keys_list);
    Py_XDECREF(dels_list);
    return rc;
}

/* Parse one TransactionAction dict; returns the action result tuple or
 * NULL with no exception for malformed (caller flags BAD_PAYLOAD), or
 * NULL with exception set for allocation failures. */
static PyObject *do_action(cur_t *c, int *malformed)
{
    uint32_t nent;
    *malformed = 0;
    if (dict_enter(c, &nent) < 0) { *malformed = 1; return NULL; }
    const uint8_t *act_span = NULL, *ph_span = NULL;
    size_t act_n = 0, ph_n = 0;
    const uint8_t *ends_p = NULL, *ends_end = NULL;
    PyObject *cc_id = NULL, *ns_writes = NULL, *meta_writes = NULL;
    PyObject *result = NULL;

    while (nent--) {
        const uint8_t *key; uint32_t klen;
        if (dict_key(c, &key, &klen) < 0) goto malformed;
        if (key_is(key, klen, "action")) {
            /* remember the span AND walk inside for chaincode_id/rwset */
            cur_t inner;
            if (value_span(c, &act_span, &act_n) < 0) goto malformed;
            inner.p = act_span;
            inner.end = act_span + act_n;
            uint32_t na;
            if (dict_enter(&inner, &na) < 0) goto malformed;
            while (na--) {
                const uint8_t *k2; uint32_t k2len;
                if (dict_key(&inner, &k2, &k2len) < 0) goto malformed;
                if (key_is(k2, k2len, "chaincode_id")) {
                    const uint8_t *sp; uint32_t sn;
                    if (rd_str(&inner, &sp, &sn) < 0) goto malformed;
                    Py_XDECREF(cc_id);
                    cc_id = PyUnicode_DecodeUTF8((const char *)sp, sn, NULL);
                    if (!cc_id) goto malformed;
                } else if (key_is(k2, k2len, "rwset")) {
                    uint32_t nr;
                    if (dict_enter(&inner, &nr) < 0) goto malformed;
                    while (nr--) {
                        const uint8_t *k3; uint32_t k3len;
                        if (dict_key(&inner, &k3, &k3len) < 0) goto malformed;
                        if (key_is(k3, k3len, "ns")) {
                            if (inner.p >= inner.end || *inner.p != 'L')
                                goto malformed;
                            inner.p++;
                            uint32_t nns;
                            if (rd_u32(&inner, &nns) < 0) goto malformed;
                            if (!ns_writes) ns_writes = PyList_New(0);
                            if (!meta_writes) meta_writes = PyList_New(0);
                            if (!ns_writes || !meta_writes) goto fail;
                            while (nns--)
                                if (do_ns_rwset(&inner, ns_writes,
                                                meta_writes) < 0) {
                                    if (PyErr_Occurred()) goto fail;
                                    goto malformed;
                                }
                        } else {
                            if (skip_value(&inner) < 0) goto malformed;
                        }
                    }
                } else {
                    if (skip_value(&inner) < 0) goto malformed;
                }
            }
        } else if (key_is(key, klen, "proposal_hash")) {
            if (value_span(c, &ph_span, &ph_n) < 0) goto malformed;
        } else if (key_is(key, klen, "endorsements")) {
            ends_p = c->p;
            if (skip_value(c) < 0) goto malformed;
            ends_end = c->p;
        } else {
            if (skip_value(c) < 0) goto malformed;
        }
    }
    if (!act_span || !ph_span || !cc_id) goto malformed;
    if (!ns_writes) ns_writes = PyList_New(0);
    if (!meta_writes) meta_writes = PyList_New(0);
    if (!ns_writes || !meta_writes) goto fail;

    /* endorsed bytes: serde({"action": ..., "proposal_hash": ...})
     * respliced from the original spans (canonical: sorted keys) */
    {
        size_t total = 1 + 4 + (4 + 6) + act_n + (4 + 13) + ph_n;
        PyObject *endorsed = PyBytes_FromStringAndSize(NULL, (Py_ssize_t)total);
        if (!endorsed) goto fail;
        uint8_t *o = (uint8_t *)PyBytes_AS_STRING(endorsed);
        *o++ = 'D';
        *o++ = 0; *o++ = 0; *o++ = 0; *o++ = 2;
        *o++ = 0; *o++ = 0; *o++ = 0; *o++ = 6;
        memcpy(o, "action", 6); o += 6;
        memcpy(o, act_span, act_n); o += act_n;
        *o++ = 0; *o++ = 0; *o++ = 0; *o++ = 13;
        memcpy(o, "proposal_hash", 13); o += 13;
        memcpy(o, ph_span, ph_n); o += ph_n;

        /* midstate over the endorsed bytes, finalized per endorser */
        sha256_t mid;
        sha256_init(&mid);
        sha256_update(&mid, (const uint8_t *)PyBytes_AS_STRING(endorsed),
                      total);

        PyObject *ends_list = PyList_New(0);
        if (!ends_list) { Py_DECREF(endorsed); goto fail; }
        if (ends_p) {
            cur_t e = {ends_p, ends_end};
            uint32_t ne;
            if (e.p >= e.end || *e.p != 'L') {
                Py_DECREF(endorsed); Py_DECREF(ends_list); goto malformed;
            }
            e.p++;
            if (rd_u32(&e, &ne) < 0) {
                Py_DECREF(endorsed); Py_DECREF(ends_list); goto malformed;
            }
            while (ne--) {
                uint32_t nent2;
                const uint8_t *edr_p = NULL, *sig_p = NULL;
                uint32_t edr_n = 0, sig_n = 0;
                if (dict_enter(&e, &nent2) < 0) {
                    Py_DECREF(endorsed); Py_DECREF(ends_list); goto malformed;
                }
                int bad = 0;
                while (nent2--) {
                    const uint8_t *k2; uint32_t k2len;
                    if (dict_key(&e, &k2, &k2len) < 0) { bad = 1; break; }
                    if (key_is(k2, k2len, "endorser")) {
                        if (rd_bytes(&e, &edr_p, &edr_n) < 0) { bad=1; break; }
                    } else if (key_is(k2, k2len, "signature")) {
                        if (rd_bytes(&e, &sig_p, &sig_n) < 0) { bad=1; break; }
                    } else {
                        if (skip_value(&e) < 0) { bad = 1; break; }
                    }
                }
                if (bad || !edr_p || !sig_p) {
                    Py_DECREF(endorsed); Py_DECREF(ends_list); goto malformed;
                }
                sha256_t fin = mid;
                uint8_t digest[32];
                sha256_update(&fin, edr_p, edr_n);
                sha256_final(&fin, digest);
                PyObject *tup = Py_BuildValue(
                    "(y#y#y#)", (const char *)edr_p, (Py_ssize_t)edr_n,
                    (const char *)sig_p, (Py_ssize_t)sig_n,
                    (const char *)digest, (Py_ssize_t)32);
                if (!tup || PyList_Append(ends_list, tup) < 0) {
                    Py_XDECREF(tup); Py_DECREF(endorsed);
                    Py_DECREF(ends_list); goto fail;
                }
                Py_DECREF(tup);
            }
        }
        result = PyTuple_New(5);
        if (!result) {
            Py_DECREF(endorsed); Py_DECREF(ends_list); goto fail;
        }
        Py_INCREF(cc_id);
        PyTuple_SET_ITEM(result, 0, cc_id);
        PyTuple_SET_ITEM(result, 1, endorsed);
        PyTuple_SET_ITEM(result, 2, ends_list);
        PyTuple_SET_ITEM(result, 3, ns_writes);
        PyTuple_SET_ITEM(result, 4, meta_writes);
        ns_writes = meta_writes = NULL;   /* ownership moved */
    }
    Py_DECREF(cc_id);
    return result;

malformed:
    *malformed = 1;
fail:
    Py_XDECREF(cc_id);
    Py_XDECREF(ns_writes);
    Py_XDECREF(meta_writes);
    return NULL;
}

/* collect one envelope -> int code or result tuple */
static PyObject *collect_env(const uint8_t *env, size_t env_n,
                             const uint8_t *chan, size_t chan_n)
{
    if (env_n == 0)
        return PyLong_FromLong(E_NIL_ENVELOPE);
    /* strict canonical gate over the whole envelope (payload is a 'B'
     * blob at this level; its interior is checked after extraction) —
     * the Python path's strict serde.decode does the same */
    if (canon_span(env, env_n) < 0)
        return PyLong_FromLong(E_BAD_PAYLOAD);
    cur_t c = {env, env + env_n};
    uint32_t nent;
    const uint8_t *payload_p = NULL, *sig_p = NULL;
    uint32_t payload_n = 0, sig_n = 0;
    if (dict_enter(&c, &nent) < 0)
        return PyLong_FromLong(E_BAD_PAYLOAD);
    while (nent--) {
        const uint8_t *key; uint32_t klen;
        if (dict_key(&c, &key, &klen) < 0)
            return PyLong_FromLong(E_BAD_PAYLOAD);
        if (key_is(key, klen, "payload")) {
            if (rd_bytes(&c, &payload_p, &payload_n) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
        } else if (key_is(key, klen, "signature")) {
            if (rd_bytes(&c, &sig_p, &sig_n) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
        } else {
            if (skip_value(&c) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
        }
    }
    if (!payload_p || !sig_p || c.p != c.end)
        return PyLong_FromLong(E_BAD_PAYLOAD);

    /* strict canonical gate over the payload BEFORE any use of it —
     * matches the Python path, which serde.decode()s the payload (and
     * would raise) before the channel/txid checks */
    if (canon_span(payload_p, payload_n) < 0)
        return PyLong_FromLong(E_BAD_PAYLOAD);

    /* payload: {"data": ..., "header": {...}} */
    cur_t pc = {payload_p, payload_p + payload_n};
    const uint8_t *data_p = NULL, *data_end = NULL;
    const uint8_t *type_p = NULL, *chanid_p = NULL, *txid_p = NULL;
    uint32_t type_n = 0, chanid_n = 0, txid_n = 0;
    const uint8_t *creator_p = NULL, *nonce_p = NULL;
    uint32_t creator_n = 0, nonce_n = 0;
    if (dict_enter(&pc, &nent) < 0)
        return PyLong_FromLong(E_BAD_PAYLOAD);
    while (nent--) {
        const uint8_t *key; uint32_t klen;
        if (dict_key(&pc, &key, &klen) < 0)
            return PyLong_FromLong(E_BAD_PAYLOAD);
        if (key_is(key, klen, "data")) {
            data_p = pc.p;
            if (skip_value(&pc) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
            data_end = pc.p;
        } else if (key_is(key, klen, "header")) {
            uint32_t nh;
            if (dict_enter(&pc, &nh) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
            while (nh--) {
                const uint8_t *k2; uint32_t k2len;
                if (dict_key(&pc, &k2, &k2len) < 0)
                    return PyLong_FromLong(E_BAD_PAYLOAD);
                if (key_is(k2, k2len, "channel_header")) {
                    uint32_t nc;
                    if (dict_enter(&pc, &nc) < 0)
                        return PyLong_FromLong(E_BAD_PAYLOAD);
                    while (nc--) {
                        const uint8_t *k3; uint32_t k3len;
                        if (dict_key(&pc, &k3, &k3len) < 0)
                            return PyLong_FromLong(E_BAD_PAYLOAD);
                        int rc2 = 0;
                        if (key_is(k3, k3len, "type"))
                            rc2 = rd_str(&pc, &type_p, &type_n);
                        else if (key_is(k3, k3len, "channel_id"))
                            rc2 = rd_str(&pc, &chanid_p, &chanid_n);
                        else if (key_is(k3, k3len, "txid"))
                            rc2 = rd_str(&pc, &txid_p, &txid_n);
                        else
                            rc2 = skip_value(&pc);
                        if (rc2 < 0)
                            return PyLong_FromLong(E_BAD_PAYLOAD);
                    }
                } else if (key_is(k2, k2len, "signature_header")) {
                    uint32_t ns;
                    if (dict_enter(&pc, &ns) < 0)
                        return PyLong_FromLong(E_BAD_PAYLOAD);
                    while (ns--) {
                        const uint8_t *k3; uint32_t k3len;
                        if (dict_key(&pc, &k3, &k3len) < 0)
                            return PyLong_FromLong(E_BAD_PAYLOAD);
                        int rc2 = 0;
                        if (key_is(k3, k3len, "creator"))
                            rc2 = rd_bytes(&pc, &creator_p, &creator_n);
                        else if (key_is(k3, k3len, "nonce"))
                            rc2 = rd_bytes(&pc, &nonce_p, &nonce_n);
                        else
                            rc2 = skip_value(&pc);
                        if (rc2 < 0)
                            return PyLong_FromLong(E_BAD_PAYLOAD);
                    }
                } else {
                    if (skip_value(&pc) < 0)
                        return PyLong_FromLong(E_BAD_PAYLOAD);
                }
            }
        } else {
            if (skip_value(&pc) < 0)
                return PyLong_FromLong(E_BAD_PAYLOAD);
        }
    }
    if (!type_p || !chanid_p || !txid_p || !creator_p || !nonce_p)
        return PyLong_FromLong(E_BAD_PAYLOAD);

    if (chanid_n != chan_n || memcmp(chanid_p, chan, chan_n) != 0)
        return PyLong_FromLong(E_TARGET_CHAIN);

    /* txid == hex(sha256(nonce || creator))  (protoutil.ComputeTxID) */
    {
        sha256_t s;
        uint8_t digest[32];
        char hex[64];
        sha256_init(&s);
        sha256_update(&s, nonce_p, nonce_n);
        sha256_update(&s, creator_p, creator_n);
        sha256_final(&s, digest);
        for (int i = 0; i < 32; i++) {
            hex[2*i] = HEXD[digest[i] >> 4];
            hex[2*i+1] = HEXD[digest[i] & 15];
        }
        if (txid_n != 64 || memcmp(txid_p, hex, 64) != 0)
            return PyLong_FromLong(E_BAD_TXID);
    }

    /* Failures from here on happen AFTER the txid is known-good: the
     * Python reference path registers the txid in seen_txids BEFORE
     * type/body validation, so later duplicates of such a tx must
     * still flag DUPLICATE_TXID.  These return (code, txid) pairs so
     * the Python tail can register the txid first — bare-int codes
     * are strictly pre-registration failures. */
#define LATE_ERR(code)  Py_BuildValue("(is#)", (code), \
        (const char *)txid_p, (Py_ssize_t)txid_n)

    int is_config = key_is(type_p, type_n, "config");
    if (!is_config && !key_is(type_p, type_n, "endorser_transaction"))
        return LATE_ERR(E_UNKNOWN_TYPE);

    PyObject *actions = NULL;
    if (!is_config) {
        /* data: {"actions": [TransactionAction...]} */
        if (!data_p)
            return LATE_ERR(E_BAD_PAYLOAD);
        cur_t dc = {data_p, data_end};
        uint32_t nd;
        const uint8_t *acts_p = NULL, *acts_end = NULL;
        if (dict_enter(&dc, &nd) < 0)
            return LATE_ERR(E_BAD_PAYLOAD);
        while (nd--) {
            const uint8_t *key; uint32_t klen;
            if (dict_key(&dc, &key, &klen) < 0)
                return LATE_ERR(E_BAD_PAYLOAD);
            if (key_is(key, klen, "actions")) {
                acts_p = dc.p;
                if (skip_value(&dc) < 0)
                    return LATE_ERR(E_BAD_PAYLOAD);
                acts_end = dc.p;
            } else {
                if (skip_value(&dc) < 0)
                    return LATE_ERR(E_BAD_PAYLOAD);
            }
        }
        if (!acts_p)
            return LATE_ERR(E_BAD_PAYLOAD);
        cur_t ac = {acts_p, acts_end};
        uint32_t na;
        if (ac.p >= ac.end || *ac.p != 'L')
            return LATE_ERR(E_BAD_PAYLOAD);
        ac.p++;
        if (rd_u32(&ac, &na) < 0)
            return LATE_ERR(E_BAD_PAYLOAD);
        if (na == 0)
            return LATE_ERR(E_NIL_TXACTION);
        actions = PyList_New(0);
        if (!actions)
            return NULL;
        while (na--) {
            int malformed = 0;
            PyObject *act = do_action(&ac, &malformed);
            if (!act) {
                Py_DECREF(actions);
                if (malformed && !PyErr_Occurred())
                    return LATE_ERR(E_BAD_PAYLOAD);
                return NULL;
            }
            if (PyList_Append(actions, act) < 0) {
                Py_DECREF(act);
                Py_DECREF(actions);
                return NULL;
            }
            Py_DECREF(act);
        }
    } else {
        actions = Py_None;
        Py_INCREF(actions);
    }

    uint8_t pd[32];
    sha256_oneshot(payload_p, payload_n, pd);

    PyObject *result = Py_BuildValue(
        "(is#y#y#y#y#N)",
        is_config ? 0 : 1,
        (const char *)txid_p, (Py_ssize_t)txid_n,
        (const char *)creator_p, (Py_ssize_t)creator_n,
        (const char *)payload_p, (Py_ssize_t)payload_n,
        (const char *)pd, (Py_ssize_t)32,
        (const char *)sig_p, (Py_ssize_t)sig_n,
        actions);
    if (!result)
        Py_DECREF(actions);
    return result;
}

static PyObject *py_collect(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *envs;
    const char *chan;
    Py_ssize_t chan_n;
    if (!PyArg_ParseTuple(args, "Os#", &envs, &chan, &chan_n))
        return NULL;
    PyObject *seq = PySequence_Fast(envs, "collect() needs a sequence");
    if (!seq)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    PyObject *out = PyList_New(n);
    if (!out) {
        Py_DECREF(seq);
        return NULL;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        /* Yield the GIL periodically: this walk runs for hundreds of
         * ms on a 10k-tx block, and the Python threads that feed the
         * device (speculative verifier, other channels' dispatches)
         * would otherwise starve through pass-1 instead of
         * overlapping it. */
        if ((i & 63) == 63) {
            Py_BEGIN_ALLOW_THREADS
            Py_END_ALLOW_THREADS
        }
        PyObject *env = PySequence_Fast_GET_ITEM(seq, i);
        PyObject *r;
        if (env == Py_None) {
            r = PyLong_FromLong(E_NIL_ENVELOPE);
        } else if (PyBytes_Check(env)) {
            char *cp;
            Py_ssize_t en;
            if (PyBytes_AsStringAndSize(env, &cp, &en) < 0) {
                Py_DECREF(seq);
                Py_DECREF(out);
                return NULL;
            }
            r = collect_env((const uint8_t *)cp, (size_t)en,
                            (const uint8_t *)chan, (size_t)chan_n);
        } else {
            /* any contiguous buffer (memoryview span from the zero-copy
             * ingest path) — same walk, no intermediate bytes copy */
            Py_buffer vb;
            if (PyObject_GetBuffer(env, &vb, PyBUF_CONTIG_RO) < 0) {
                Py_DECREF(seq);
                Py_DECREF(out);
                return NULL;
            }
            r = collect_env((const uint8_t *)vb.buf, (size_t)vb.len,
                            (const uint8_t *)chan, (size_t)chan_n);
            PyBuffer_Release(&vb);
        }
        if (!r) {
            Py_DECREF(seq);
            Py_DECREF(out);
            return NULL;
        }
        PyList_SET_ITEM(out, i, r);
    }
    Py_DECREF(seq);
    return out;
}

/* ------------------------------------------------------------------ */
/* Digested pass-1 tail + verdict gate (the deep native path).
 *
 * digest()   walks every envelope (same collect_env walker as collect())
 *            but CONSUMES the per-tx tuples in C: txid dedup against a
 *            C-side seen dict (plus the pipelined carry window and the
 *            ledger oracle), the config-multi check, and first-seen-order
 *            SLOT assignment for unique creator/endorser identity bytes.
 *            Python only resolves each unique identity once (MSP
 *            deserialize + chain validation) instead of running a ~10k
 *            iteration bytecode loop per block.
 * assemble() turns digested works + resolved identity slots into the
 *            block's signature table (SigTable below: every P-256 item
 *            a row of flat buffers — digest, r||s, key index, dispatch
 *            position — deduplicated in C, no VerifyItem and no
 *            container an item; items of another shape interned as
 *            VerifyItems beside it) and per-tx gate plans.
 * gate()     folds the device verdict bitmap into final ValidationCodes
 *            with the same memoized policy-evaluation semantics as
 *            txvalidator._gate_tx/_memoized_plugin, no per-tx Python.
 *
 * The Python tail (_collect_tx_fast/_gate_tx) stays as the line-for-line
 * mirror and the key-level-endorsement path (digest's n_meta is one of
 * the three things the validator's per-block rule reads); both must
 * produce bit-identical TxFlags
 * (state-fork invariant).  ValidationCode values are mirrored from
 * protocol/txflags.py below — guarded by the differential tests.
 */

#define VC_VALID            0
#define VC_BAD_CREATOR      4
#define VC_INVALID_CONFIG   6
#define VC_DUPLICATE        9
#define VC_POLICY_FAIL     10
#define VC_INVALID_CC      25
#define VC_NOT_VALIDATED  254

/* fastcollect E_* structural code -> ValidationCode (txvalidator._FC_CODES):
 * NIL_ENVELOPE=1 BAD_PAYLOAD=2 TARGET_CHAIN_NOT_FOUND=14
 * BAD_PROPOSAL_TXID=8 UNKNOWN_TX_TYPE=13 NIL_TXACTION=16 */
static const uint8_t FC2VC[7] = {0, 1, 2, 14, 8, 13, 16};

static PyObject *s_verify_item;     /* interned "verify_item" */

/* 1 = duplicate, 0 = fresh, -1 = error.  Order matches the Python tail:
 * own block's seen dict, then the in-flight carry maps, then the ledger
 * oracle (None when the validator is unwired — skips the call). */
static int txid_is_dup(PyObject *txid, PyObject *seen, PyObject *carry,
                       Py_ssize_t ncarry, PyObject *oracle)
{
    int r = PyDict_Contains(seen, txid);
    if (r != 0)
        return r;
    for (Py_ssize_t i = 0; i < ncarry; i++) {
        r = PyDict_Contains(PyList_GET_ITEM(carry, i), txid);
        if (r != 0)
            return r;
    }
    if (oracle != Py_None) {
        PyObject *res = PyObject_CallFunctionObjArgs(oracle, txid, NULL);
        if (!res)
            return -1;
        r = PyObject_IsTrue(res);
        Py_DECREF(res);
        return r;
    }
    return 0;
}

/* first-seen-order slot assignment: map[key] -> slot, appending key to
 * list on first sight.  Returns slot, or -1 with an exception set. */
static Py_ssize_t slot_of(PyObject *map, PyObject *list, PyObject *key)
{
    PyObject *v = PyDict_GetItemWithError(map, key);
    if (v)
        return PyLong_AsSsize_t(v);
    if (PyErr_Occurred())
        return -1;
    Py_ssize_t slot = PyList_GET_SIZE(list);
    PyObject *iv = PyLong_FromSsize_t(slot);
    if (!iv)
        return -1;
    int rc = PyDict_SetItem(map, key, iv);
    Py_DECREF(iv);
    if (rc < 0 || PyList_Append(list, key) < 0)
        return -1;
    return slot;
}

/* walker actions [(cc_id, endorsed, ends, ns_writes, meta), ...] ->
 * digested [(cc_id, endorsed, [(eslot, esig, edigest)...], ns_names)].
 * Endorsements dedup by endorser bytes per action (policy.go:385-387,
 * first kept) BEFORE slot assignment — exactly the Python tail's
 * seen_idents order.  ns_names = sorted({cc_id} | write ns | meta base)
 * (the namespace set where no key has a validation parameter: the deep
 * path is taken only for a block key-level endorsement cannot touch, and
 * *n_meta, the "#meta" writes seen, is how the caller learns that this
 * block is not one). */
static PyObject *digest_actions(PyObject *acts, PyObject *emap,
                                PyObject *endorsers, Py_ssize_t *n_meta)
{
    Py_ssize_t na = PyList_GET_SIZE(acts);
    PyObject *out = PyList_New(na);
    if (!out)
        return NULL;
    for (Py_ssize_t a = 0; a < na; a++) {
        PyObject *act = PyList_GET_ITEM(acts, a);
        PyObject *cc = PyTuple_GET_ITEM(act, 0);
        PyObject *endorsed = PyTuple_GET_ITEM(act, 1);
        PyObject *ends = PyTuple_GET_ITEM(act, 2);
        PyObject *ns_writes = PyTuple_GET_ITEM(act, 3);
        PyObject *meta = PyTuple_GET_ITEM(act, 4);
        PyObject *ns_set = NULL, *ns_names = NULL, *eseen = NULL,
                 *ends2 = NULL, *act2 = NULL;
        *n_meta += PyList_GET_SIZE(meta);
        ns_set = PyDict_New();
        if (!ns_set)
            goto fail;
        if (PyDict_SetItem(ns_set, cc, Py_None) < 0)
            goto fail;
        for (Py_ssize_t w = 0; w < PyList_GET_SIZE(ns_writes); w++)
            if (PyDict_SetItem(ns_set,
                    PyTuple_GET_ITEM(PyList_GET_ITEM(ns_writes, w), 0),
                    Py_None) < 0)
                goto fail;
        for (Py_ssize_t m = 0; m < PyList_GET_SIZE(meta); m++)
            if (PyDict_SetItem(ns_set,
                    PyTuple_GET_ITEM(PyList_GET_ITEM(meta, m), 0),
                    Py_None) < 0)
                goto fail;
        ns_names = PyDict_Keys(ns_set);
        Py_CLEAR(ns_set);
        if (!ns_names || PyList_Sort(ns_names) < 0)
            goto fail;
        eseen = PyDict_New();
        ends2 = PyList_New(0);
        if (!eseen || !ends2)
            goto fail;
        for (Py_ssize_t e = 0; e < PyList_GET_SIZE(ends); e++) {
            PyObject *end3 = PyList_GET_ITEM(ends, e);
            PyObject *edr = PyTuple_GET_ITEM(end3, 0);
            int dup = PyDict_Contains(eseen, edr);
            if (dup < 0)
                goto fail;
            if (dup)
                continue;
            if (PyDict_SetItem(eseen, edr, Py_None) < 0)
                goto fail;
            Py_ssize_t slot = slot_of(emap, endorsers, edr);
            if (slot < 0)
                goto fail;
            PyObject *slo = PyLong_FromSsize_t(slot);
            if (!slo)
                goto fail;
            PyObject *t = PyTuple_New(3);
            if (!t) { Py_DECREF(slo); goto fail; }
            PyTuple_SET_ITEM(t, 0, slo);
            Py_INCREF(PyTuple_GET_ITEM(end3, 1));
            PyTuple_SET_ITEM(t, 1, PyTuple_GET_ITEM(end3, 1));
            Py_INCREF(PyTuple_GET_ITEM(end3, 2));
            PyTuple_SET_ITEM(t, 2, PyTuple_GET_ITEM(end3, 2));
            int rc = PyList_Append(ends2, t);
            Py_DECREF(t);
            if (rc < 0)
                goto fail;
        }
        Py_CLEAR(eseen);
        act2 = PyTuple_New(4);
        if (!act2)
            goto fail;
        Py_INCREF(cc);
        PyTuple_SET_ITEM(act2, 0, cc);
        Py_INCREF(endorsed);
        PyTuple_SET_ITEM(act2, 1, endorsed);
        PyTuple_SET_ITEM(act2, 2, ends2);
        PyTuple_SET_ITEM(act2, 3, ns_names);
        ends2 = ns_names = NULL;            /* ownership moved */
        PyList_SET_ITEM(out, a, act2);
        continue;
    fail:
        Py_XDECREF(ns_set);
        Py_XDECREF(ns_names);
        Py_XDECREF(eseen);
        Py_XDECREF(ends2);
        Py_DECREF(out);
        return NULL;
    }
    return out;
}

/* digest(envs, channel_id, carry, oracle)
 *   -> (codes: bytearray, seen: {txid: tx_num}, works, creators, endorsers,
 *       n_meta)
 *
 * codes[i] is the FINAL ValidationCode for structurally-dead txs and
 * VC_NOT_VALIDATED (254) for live works.  works[j] =
 * (tx_num, txtype, creator_slot, payload, pdigest, signature, acts|None);
 * creators/endorsers are first-seen-ordered unique identity bytes whose
 * MSP resolution the Python caller performs once per slot.  n_meta counts
 * the writes to "<ns>#meta" namespaces (validation parameters set or
 * deleted) in the live works: 0 says no tx of this block can change a
 * key's policy for a later one.
 *
 * Two envelope sources share one implementation: a Python sequence of
 * bytes objects (digest(), the classic entry), or a zero-copy span
 * table over one base buffer (digest_spans(), fed straight from
 * native/fastparse.c block parses — no per-tx bytes objects exist). */
static PyObject *digest_impl(PyObject *seq,
                             const uint8_t *base, size_t base_n,
                             const uint8_t *spans, Py_ssize_t nspans,
                             const char *chan, Py_ssize_t chan_n,
                             PyObject *carry_in, PyObject *oracle)
{
    PyObject *carry = NULL, *codes = NULL, *seen = NULL,
             *works = NULL, *creators = NULL, *endorsers = NULL,
             *cmap = NULL, *emap = NULL, *ret = NULL, *metao = NULL;
    Py_ssize_t n_meta = 0;
    carry = PySequence_List(carry_in);
    if (!carry)
        goto done;
    Py_ssize_t ncarry = PyList_GET_SIZE(carry);
    for (Py_ssize_t i = 0; i < ncarry; i++)
        if (!PyDict_Check(PyList_GET_ITEM(carry, i))) {
            PyErr_SetString(PyExc_TypeError,
                            "digest() carry entries must be dicts");
            goto done;
        }
    Py_ssize_t n = seq ? PySequence_Fast_GET_SIZE(seq) : nspans;
    codes = PyByteArray_FromStringAndSize(NULL, n);
    seen = PyDict_New();
    works = PyList_New(0);
    creators = PyList_New(0);
    endorsers = PyList_New(0);
    cmap = PyDict_New();
    emap = PyDict_New();
    if (!codes || !seen || !works || !creators || !endorsers || !cmap
        || !emap)
        goto done;
    uint8_t *cp = (uint8_t *)PyByteArray_AS_STRING(codes);
    memset(cp, VC_NOT_VALIDATED, (size_t)n);

    for (Py_ssize_t i = 0; i < n; i++) {
        if ((i & 63) == 63) {         /* keep device pump threads fed */
            Py_BEGIN_ALLOW_THREADS
            Py_END_ALLOW_THREADS
        }
        PyObject *rec;
        if (seq) {
            PyObject *env = PySequence_Fast_GET_ITEM(seq, i);
            if (env == Py_None) {
                cp[i] = FC2VC[E_NIL_ENVELOPE];
                continue;
            }
            char *ep;
            Py_ssize_t en;
            if (PyBytes_AsStringAndSize(env, &ep, &en) < 0)
                goto done;
            rec = collect_env((const uint8_t *)ep, (size_t)en,
                              (const uint8_t *)chan, (size_t)chan_n);
        } else {
            uint64_t off, ln;
            memcpy(&off, spans + 16 * i, 8);
            memcpy(&ln, spans + 16 * i + 8, 8);
            if (off > base_n || ln > base_n - off) {
                PyErr_SetString(PyExc_ValueError,
                                "digest_spans: span out of range");
                goto done;
            }
            rec = collect_env(base + off, (size_t)ln,
                              (const uint8_t *)chan, (size_t)chan_n);
        }
        if (!rec)
            goto done;
        if (PyLong_Check(rec)) {      /* pre-registration failure */
            long code = PyLong_AsLong(rec);
            Py_DECREF(rec);
            cp[i] = FC2VC[code];
            continue;
        }
        Py_ssize_t rlen = PyTuple_GET_SIZE(rec);
        PyObject *txid = PyTuple_GET_ITEM(rec, 1);
        int dup = txid_is_dup(txid, seen, carry, ncarry, oracle);
        if (dup < 0) { Py_DECREF(rec); goto done; }
        if (dup) {
            cp[i] = VC_DUPLICATE;
            Py_DECREF(rec);
            continue;
        }
        {
            PyObject *num = PyLong_FromSsize_t(i);
            int rc = num ? PyDict_SetItem(seen, txid, num) : -1;
            Py_XDECREF(num);
            if (rc < 0) { Py_DECREF(rec); goto done; }
        }
        if (rlen == 2) {              /* post-registration failure */
            long code = PyLong_AsLong(PyTuple_GET_ITEM(rec, 0));
            Py_DECREF(rec);
            cp[i] = FC2VC[code];
            continue;
        }
        long txtype = PyLong_AsLong(PyTuple_GET_ITEM(rec, 0));
        if (txtype == 0 && n != 1) {  /* config tx in a multi-tx block */
            cp[i] = VC_INVALID_CONFIG;
            Py_DECREF(rec);
            continue;
        }
        Py_ssize_t cslot = slot_of(cmap, creators,
                                   PyTuple_GET_ITEM(rec, 2));
        if (cslot < 0) { Py_DECREF(rec); goto done; }
        PyObject *acts_in = PyTuple_GET_ITEM(rec, 6);
        PyObject *acts2;
        if (acts_in == Py_None) {
            acts2 = Py_None;
            Py_INCREF(acts2);
        } else {
            acts2 = digest_actions(acts_in, emap, endorsers, &n_meta);
            if (!acts2) { Py_DECREF(rec); goto done; }
        }
        PyObject *work = PyTuple_New(7);
        PyObject *txo = PyLong_FromSsize_t(i);
        PyObject *typo = PyLong_FromLong(txtype);
        PyObject *cso = PyLong_FromSsize_t(cslot);
        if (!work || !txo || !typo || !cso) {
            Py_XDECREF(work); Py_XDECREF(txo); Py_XDECREF(typo);
            Py_XDECREF(cso); Py_DECREF(acts2); Py_DECREF(rec);
            goto done;
        }
        PyTuple_SET_ITEM(work, 0, txo);
        PyTuple_SET_ITEM(work, 1, typo);
        PyTuple_SET_ITEM(work, 2, cso);
        Py_INCREF(PyTuple_GET_ITEM(rec, 3));
        PyTuple_SET_ITEM(work, 3, PyTuple_GET_ITEM(rec, 3));  /* payload */
        Py_INCREF(PyTuple_GET_ITEM(rec, 4));
        PyTuple_SET_ITEM(work, 4, PyTuple_GET_ITEM(rec, 4));  /* pdigest */
        Py_INCREF(PyTuple_GET_ITEM(rec, 5));
        PyTuple_SET_ITEM(work, 5, PyTuple_GET_ITEM(rec, 5));  /* signature */
        PyTuple_SET_ITEM(work, 6, acts2);
        Py_DECREF(rec);
        int rc = PyList_Append(works, work);
        Py_DECREF(work);
        if (rc < 0)
            goto done;
    }
    metao = PyLong_FromSsize_t(n_meta);
    ret = metao ? PyTuple_New(6) : NULL;
    if (!ret)
        goto done;
    PyTuple_SET_ITEM(ret, 5, metao);
    metao = NULL;
    PyTuple_SET_ITEM(ret, 0, codes);
    PyTuple_SET_ITEM(ret, 1, seen);
    PyTuple_SET_ITEM(ret, 2, works);
    PyTuple_SET_ITEM(ret, 3, creators);
    PyTuple_SET_ITEM(ret, 4, endorsers);
    codes = seen = works = creators = endorsers = NULL;
done:
    Py_XDECREF(carry);
    Py_XDECREF(codes);
    Py_XDECREF(seen);
    Py_XDECREF(works);
    Py_XDECREF(creators);
    Py_XDECREF(endorsers);
    Py_XDECREF(cmap);
    Py_XDECREF(emap);
    Py_XDECREF(metao);
    return ret;
}

static PyObject *py_digest(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *envs, *carry_in, *oracle;
    const char *chan;
    Py_ssize_t chan_n;
    if (!PyArg_ParseTuple(args, "Os#OO", &envs, &chan, &chan_n,
                          &carry_in, &oracle))
        return NULL;
    PyObject *seq = PySequence_Fast(envs, "digest() needs a sequence");
    if (!seq)
        return NULL;
    PyObject *ret = digest_impl(seq, NULL, 0, NULL, 0, chan, chan_n,
                                carry_in, oracle);
    Py_DECREF(seq);
    return ret;
}

/* digest_spans(base, spans, channel_id, carry, oracle) — identical
 * result to digest([base[off:off+len] for off, len in spans], ...) but
 * the envelopes are consumed in place: `spans` is a buffer of
 * native-endian (u64 off, u64 len) pairs into `base` (the layout
 * fastparse.parse_block emits). */
static PyObject *py_digest_spans(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *base_o, *spans_o, *carry_in, *oracle;
    const char *chan;
    Py_ssize_t chan_n;
    if (!PyArg_ParseTuple(args, "OOs#OO", &base_o, &spans_o, &chan,
                          &chan_n, &carry_in, &oracle))
        return NULL;
    Py_buffer base_v, spans_v;
    if (PyObject_GetBuffer(base_o, &base_v, PyBUF_CONTIG_RO) < 0)
        return NULL;
    if (PyObject_GetBuffer(spans_o, &spans_v, PyBUF_CONTIG_RO) < 0) {
        PyBuffer_Release(&base_v);
        return NULL;
    }
    PyObject *ret = NULL;
    if (spans_v.len % 16) {
        PyErr_SetString(PyExc_ValueError,
                        "digest_spans: spans length not a multiple of 16");
    } else {
        ret = digest_impl(NULL, (const uint8_t *)base_v.buf,
                          (size_t)base_v.len,
                          (const uint8_t *)spans_v.buf, spans_v.len / 16,
                          chan, chan_n, carry_in, oracle);
    }
    PyBuffer_Release(&spans_v);
    PyBuffer_Release(&base_v);
    return ret;
}

/* The signature table: what the deep tail hands the provider.
 *
 * A block's unique verify items in dispatch order, WITHOUT a Python
 * object an item.  Every item that is P-256's four plain fields (the
 * scheme, a public key's wire bytes, a DER signature, the walker's
 * 32-byte SHA-256) is one ROW of five flat buffers:
 *
 *   digest u8[n,32]   the digest the signature is over
 *   rs     u8[n,64]   r32be || s32be, the DER parsed where it is read
 *                     (der_sig64: parse_der_sigs' own rule); zeros and
 *   ok     u8[n]      0 where it does not parse
 *   key    i32[n]     index into `keys`, the block's unique public keys
 *   pos    i32[n]     the item's dispatch position, ascending
 *
 * and an item of any other shape (Ed25519 over the message, idemix, a
 * digest that is not 32 bytes) stays a VerifyItem in the short list
 * `rest`, its position in `rest_pos` (i32, ascending).  Positions run
 * over both, 0 .. len(table)-1, in the order the Python tail would
 * intern the items: the verdicts come back aligned with them.
 *
 * Dedup is exact and lives here: rows in an open-addressed set keyed on
 * the digest's first 8 bytes mixed with the signature's hash (the
 * interpreter's keyed one, cached on the bytes object: a block of equal
 * digests under crafted signatures cannot chain the probes), a full
 * compare — key, digest, signature bytes — on a match; the rest in a
 * dict, as the whole block was before.  The table IS a sequence of
 * VerifyItems: len(), table[i] and iteration build the item at a
 * position on demand (`cls(scheme, key, signature, digest)`), which is
 * how the verdict cache's probe reads 256 of them and how a provider
 * without the packed verb gets them all.  Built by assemble() and by
 * pack_items(); never from Python. */

typedef struct {
    PyObject_HEAD
    Py_ssize_t n_rows;          /* rows written */
    Py_ssize_t cap;             /* rows the buffers hold until sealed */
    Py_ssize_t n_pos;           /* positions given out: rows + rest */
    PyObject *digest, *rs, *ok, *key, *pos;     /* bytes, see above */
    PyObject *keys;             /* list of wire bytes */
    PyObject *rest, *rest_pos;  /* list of items; bytes i32[len(rest)]:
                                 * restmap's keys and values, when sealed */
    PyObject *keymap;           /* wire -> key id; dropped when sealed */
    PyObject *restmap;          /* item -> position; dropped when sealed */
    PyObject **sig, **pay;      /* a row's signature and digest objects */
    PyObject *cls, *scheme;     /* VerifyItem, SCHEME_P256 */
    int32_t *set;               /* dedup set of rows; freed when sealed */
    size_t mask;
} SigTable;

static PyTypeObject SigTableType;

static int der_sig64(const uint8_t *p, Py_ssize_t sn, uint8_t out[64]);

static void table_dealloc(SigTable *t)
{
    for (Py_ssize_t i = 0; t->sig && i < t->n_rows; i++) {
        Py_DECREF(t->sig[i]);
        Py_DECREF(t->pay[i]);
    }
    PyMem_Free(t->sig);
    PyMem_Free(t->pay);
    PyMem_Free(t->set);
    Py_XDECREF(t->digest); Py_XDECREF(t->rs); Py_XDECREF(t->ok);
    Py_XDECREF(t->key); Py_XDECREF(t->pos); Py_XDECREF(t->keys);
    Py_XDECREF(t->rest); Py_XDECREF(t->rest_pos);
    Py_XDECREF(t->keymap); Py_XDECREF(t->restmap);
    Py_XDECREF(t->cls); Py_XDECREF(t->scheme);
    Py_TYPE(t)->tp_free((PyObject *)t);
}

/* an empty table with room for `cap` rows */
static SigTable *table_new(Py_ssize_t cap, PyObject *cls, PyObject *scheme)
{
    SigTable *t = PyObject_New(SigTable, &SigTableType);
    if (!t)
        return NULL;
    memset((char *)t + sizeof(PyObject), 0,
           sizeof(SigTable) - sizeof(PyObject));
    Py_INCREF(cls);
    t->cls = cls;
    Py_INCREF(scheme);
    t->scheme = scheme;
    t->cap = cap;
    size_t slots = 64;
    while (slots < 2 * (size_t)cap)
        slots <<= 1;
    t->mask = slots - 1;
    t->digest = PyBytes_FromStringAndSize(NULL, cap * 32);
    t->rs = PyBytes_FromStringAndSize(NULL, cap * 64);
    t->ok = PyBytes_FromStringAndSize(NULL, cap);
    t->key = PyBytes_FromStringAndSize(NULL, cap * 4);
    t->pos = PyBytes_FromStringAndSize(NULL, cap * 4);
    t->keys = PyList_New(0);
    t->keymap = PyDict_New();
    t->restmap = PyDict_New();
    t->sig = PyMem_Malloc((size_t)(cap ? cap : 1) * sizeof(PyObject *));
    t->pay = PyMem_Malloc((size_t)(cap ? cap : 1) * sizeof(PyObject *));
    t->set = PyMem_Malloc(slots * sizeof(int32_t));
    if (!t->digest || !t->rs || !t->ok || !t->key || !t->pos || !t->keys
        || !t->keymap || !t->restmap || !t->sig || !t->pay || !t->set) {
        Py_DECREF(t);
        PyErr_NoMemory();
        return NULL;
    }
    memset(t->set, 0xff, slots * sizeof(int32_t));      /* -1: empty */
    return t;
}

/* is (signature, payload) a row's: bytes over a 32-byte digest */
static inline int row_shaped(PyObject *sig, PyObject *pay)
{
    return PyBytes_Check(sig) && PyBytes_Check(pay)
        && PyBytes_GET_SIZE(pay) == 32;
}

/* The position of the row (key `kid`, `sig`, digest `pay`), written on
 * first sight.  The caller has checked row_shaped() and that the table
 * has room.  -1 with an exception set. */
static Py_ssize_t table_add(SigTable *t, Py_ssize_t kid, PyObject *sig,
                            PyObject *pay)
{
    const uint8_t *dg = (const uint8_t *)PyBytes_AS_STRING(pay);
    const uint8_t *sp = (const uint8_t *)PyBytes_AS_STRING(sig);
    Py_ssize_t sn = PyBytes_GET_SIZE(sig);
    uint8_t *dgv = (uint8_t *)PyBytes_AS_STRING(t->digest);
    int32_t *keyv = (int32_t *)PyBytes_AS_STRING(t->key);
    int32_t *posv = (int32_t *)PyBytes_AS_STRING(t->pos);
    Py_hash_t sh = PyObject_Hash(sig);
    if (sh == -1 && PyErr_Occurred())
        return -1;
    uint64_t h;
    memcpy(&h, dg, 8);
    h ^= ((uint64_t)sh + (uint64_t)kid) * 0x9E3779B97F4A7C15ull;
    h ^= h >> 32;
    size_t i = (size_t)h & t->mask;
    for (;; i = (i + 1) & t->mask) {
        int32_t r = t->set[i];
        if (r < 0)
            break;
        if (keyv[r] == (int32_t)kid && !memcmp(dgv + 32 * r, dg, 32)
            && (t->sig[r] == sig
                || (PyBytes_GET_SIZE(t->sig[r]) == sn
                    && !memcmp(PyBytes_AS_STRING(t->sig[r]), sp,
                               (size_t)sn))))
            return posv[r];
    }
    Py_ssize_t r = t->n_rows;
    if (r >= t->cap) {
        PyErr_SetString(PyExc_SystemError, "signature table overrun");
        return -1;
    }
    uint8_t *rsv = (uint8_t *)PyBytes_AS_STRING(t->rs) + 64 * r;
    memcpy(dgv + 32 * r, dg, 32);
    ((uint8_t *)PyBytes_AS_STRING(t->ok))[r] =
        (uint8_t)der_sig64(sp, sn, rsv);
    keyv[r] = (int32_t)kid;
    posv[r] = (int32_t)t->n_pos;
    Py_INCREF(sig);
    t->sig[r] = sig;
    Py_INCREF(pay);
    t->pay[r] = pay;
    t->set[i] = (int32_t)r;
    t->n_rows = r + 1;
    return t->n_pos++;
}

/* The position of an item that is no row, interned on first sight. */
static Py_ssize_t table_add_item(SigTable *t, PyObject *item)
{
    PyObject *v = PyDict_GetItemWithError(t->restmap, item);
    if (v)
        return PyLong_AsSsize_t(v);
    if (PyErr_Occurred())
        return -1;
    PyObject *iv = PyLong_FromSsize_t(t->n_pos);
    int rc = iv ? PyDict_SetItem(t->restmap, item, iv) : -1;
    Py_XDECREF(iv);
    return rc < 0 ? -1 : t->n_pos++;
}

/* The same for an item that is four plain fields of another shape
 * (Ed25519 over the message itself): probed with a plain 4-tuple FIRST
 * (a tuple hashes and compares equal to the NamedTuple of the same
 * fields), so a repeat constructs nothing and calls nothing in Python. */
static Py_ssize_t table_add_fields(SigTable *t, PyObject *scheme,
                                   PyObject *wire, PyObject *sig,
                                   PyObject *payload)
{
    PyObject *probe = PyTuple_Pack(4, scheme, wire, sig, payload);
    if (!probe)
        return -1;
    PyObject *v = PyDict_GetItemWithError(t->restmap, probe);
    if (v) {
        Py_DECREF(probe);
        return PyLong_AsSsize_t(v);
    }
    if (PyErr_Occurred()) { Py_DECREF(probe); return -1; }
    PyObject *item = PyObject_CallObject(t->cls, probe);
    Py_DECREF(probe);
    if (!item)
        return -1;
    Py_ssize_t idx = table_add_item(t, item);
    Py_DECREF(item);
    return idx;
}

/* Close a built table: the buffers cut to the rows written, the rest
 * and its positions read off the dict that interned it (insertion
 * order = ascending positions), what only the build needed let go. */
static int table_seal(SigTable *t)
{
    Py_ssize_t n = t->n_rows, nr = PyDict_GET_SIZE(t->restmap);
    t->rest = PyList_New(nr);
    t->rest_pos = PyBytes_FromStringAndSize(NULL, nr * 4);
    if (!t->rest || !t->rest_pos)
        return -1;
    int32_t *posv = (int32_t *)PyBytes_AS_STRING(t->rest_pos);
    PyObject *item, *at;
    for (Py_ssize_t i = 0, it = 0; PyDict_Next(t->restmap, &it, &item, &at);
         i++) {
        Py_INCREF(item);
        PyList_SET_ITEM(t->rest, i, item);
        posv[i] = (int32_t)PyLong_AsSsize_t(at);
    }
    if (n < t->cap
        && (_PyBytes_Resize(&t->digest, n * 32) < 0
            || _PyBytes_Resize(&t->rs, n * 64) < 0
            || _PyBytes_Resize(&t->ok, n) < 0
            || _PyBytes_Resize(&t->key, n * 4) < 0
            || _PyBytes_Resize(&t->pos, n * 4) < 0))
        return -1;
    t->cap = n;
    PyMem_Free(t->set);
    t->set = NULL;
    Py_CLEAR(t->keymap);
    Py_CLEAR(t->restmap);
    return 0;
}

static Py_ssize_t table_len(PyObject *self)
{
    return ((SigTable *)self)->n_pos;
}

/* index of `want` in the ascending i32 vector, or -1 */
static Py_ssize_t find_pos(const int32_t *v, Py_ssize_t n, Py_ssize_t want)
{
    Py_ssize_t lo = 0, hi = n;
    while (lo < hi) {
        Py_ssize_t mid = lo + (hi - lo) / 2;
        if (v[mid] < want)
            lo = mid + 1;
        else
            hi = mid;
    }
    return (lo < n && v[lo] == want) ? lo : -1;
}

/* table[i]: the VerifyItem at dispatch position i — a row's is built
 * here, one of the rest is the one kept */
static PyObject *table_item(PyObject *self, Py_ssize_t i)
{
    SigTable *t = (SigTable *)self;
    if (i < 0 || i >= t->n_pos || !t->rest_pos) {
        PyErr_SetString(PyExc_IndexError, "signature table position");
        return NULL;
    }
    Py_ssize_t r = find_pos((const int32_t *)PyBytes_AS_STRING(t->pos),
                            t->n_rows, i);
    if (r >= 0) {
        int32_t kid = ((const int32_t *)PyBytes_AS_STRING(t->key))[r];
        return PyObject_CallFunctionObjArgs(
            t->cls, t->scheme, PyList_GET_ITEM(t->keys, kid), t->sig[r],
            t->pay[r], NULL);
    }
    r = find_pos((const int32_t *)PyBytes_AS_STRING(t->rest_pos),
                 PyList_GET_SIZE(t->rest), i);
    if (r < 0) {
        PyErr_SetString(PyExc_SystemError, "signature table position lost");
        return NULL;
    }
    PyObject *it = PyList_GET_ITEM(t->rest, r);
    Py_INCREF(it);
    return it;
}

static PySequenceMethods table_as_sequence = {
    .sq_length = table_len,
    .sq_item = table_item,
};

static PyMemberDef table_members[] = {
    {"n_rows", T_PYSSIZET, offsetof(SigTable, n_rows), READONLY,
     "rows: the items handed over as arrays"},
    {"digest", T_OBJECT_EX, offsetof(SigTable, digest), READONLY,
     "u8[n_rows,32]"},
    {"rs", T_OBJECT_EX, offsetof(SigTable, rs), READONLY,
     "u8[n_rows,64]: r32be || s32be"},
    {"ok", T_OBJECT_EX, offsetof(SigTable, ok), READONLY,
     "u8[n_rows]: the DER signature parsed"},
    {"key", T_OBJECT_EX, offsetof(SigTable, key), READONLY,
     "i32[n_rows]: index into keys"},
    {"pos", T_OBJECT_EX, offsetof(SigTable, pos), READONLY,
     "i32[n_rows]: dispatch positions, ascending"},
    {"keys", T_OBJECT_EX, offsetof(SigTable, keys), READONLY,
     "the rows' unique public keys, wire bytes"},
    {"rest", T_OBJECT_EX, offsetof(SigTable, rest), READONLY,
     "the items that are no row, VerifyItems"},
    {"rest_pos", T_OBJECT_EX, offsetof(SigTable, rest_pos), READONLY,
     "i32[len(rest)]: their dispatch positions, ascending"},
    {NULL, 0, 0, 0, NULL}};

static PyTypeObject SigTableType = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_fastcollect.SigTable",
    .tp_basicsize = sizeof(SigTable),
    .tp_dealloc = (destructor)table_dealloc,
    .tp_as_sequence = &table_as_sequence,
    .tp_flags = Py_TPFLAGS_DEFAULT | Py_TPFLAGS_DISALLOW_INSTANTIATION,
    .tp_doc = "a block's unique verify items: P-256 rows as flat "
              "buffers, the rest as VerifyItems; a sequence of "
              "VerifyItems in dispatch order",
    .tp_members = table_members,
};

/* pack_items(items, verify_item_cls, scheme_p256) -> SigTable
 *
 * The table of a sequence of VerifyItems, for a caller that has items
 * and wants the packed verb (and for the provider's differential
 * tests): an item that is a 4-tuple of `scheme_p256`, bytes, bytes and
 * a 32-byte digest becomes a row, any other stays itself.  Equal items
 * are one, at the first one's position. */
static PyObject *py_pack_items(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *items, *cls, *scheme;
    if (!PyArg_ParseTuple(args, "OOO", &items, &cls, &scheme))
        return NULL;
    PyObject *seq = PySequence_Fast(items, "pack_items needs a sequence");
    if (!seq)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    SigTable *t = table_new(n, cls, scheme);
    int fail = t == NULL;
    for (Py_ssize_t i = 0; !fail && i < n; i++) {
        PyObject *it = PySequence_Fast_GET_ITEM(seq, i);
        Py_ssize_t at;
        int row = PyTuple_Check(it) && PyTuple_GET_SIZE(it) == 4
            && PyBytes_Check(PyTuple_GET_ITEM(it, 1))
            && row_shaped(PyTuple_GET_ITEM(it, 2), PyTuple_GET_ITEM(it, 3));
        if (row)
            row = PyObject_RichCompareBool(PyTuple_GET_ITEM(it, 0), scheme,
                                           Py_EQ);
        if (row < 0) {
            at = -1;
        } else if (row) {
            at = slot_of(t->keymap, t->keys, PyTuple_GET_ITEM(it, 1));
            if (at >= 0)
                at = table_add(t, at, PyTuple_GET_ITEM(it, 2),
                               PyTuple_GET_ITEM(it, 3));
        } else {
            at = table_add_item(t, it);
        }
        fail = at < 0;
    }
    Py_DECREF(seq);
    if (!fail && table_seal(t) < 0)
        fail = 1;
    if (fail)
        Py_CLEAR(t);
    return (PyObject *)t;
}

#define KEY_UNSET (-1)      /* slot not looked at yet */
#define KEY_NONE  (-2)      /* its items are no rows */
#define KEY_ERR   (-3)

/* Which of the table's keys a resolved identity's signatures go under:
 * its id in t->keys where the identity's item is P-256's four plain
 * fields, KEY_NONE where it keeps another shape.  Decided once a slot
 * (`cache`), so an item costs an array read. */
static int32_t slot_key(SigTable *t, int32_t *cache, Py_ssize_t slot,
                        PyObject *ent)
{
    if (cache[slot] != KEY_UNSET)
        return cache[slot];
    PyObject *wire = PyTuple_GET_ITEM(ent, 1);
    int32_t k = KEY_NONE;
    if (PyBytes_Check(wire)) {
        int eq = PyObject_RichCompareBool(PyTuple_GET_ITEM(ent, 2),
                                          t->scheme, Py_EQ);
        if (eq < 0)
            return KEY_ERR;
        if (eq) {
            Py_ssize_t id = slot_of(t->keymap, t->keys, wire);
            if (id < 0)
                return KEY_ERR;
            k = (int32_t)id;
        }
    }
    cache[slot] = k;
    return k;
}

/* assemble()'s walk over the works: fills `t` and `plans`, -> n_refs or
 * -1 with an exception set */
static Py_ssize_t assemble_works(SigTable *t, int32_t *ckey, int32_t *ekey,
                                 PyObject *works, PyObject *c_ents,
                                 PyObject *e_ents, PyObject *endorsers,
                                 PyObject *codes, PyObject *plans,
                                 PyObject *policy_for, PyObject *pol_cache)
{
    PyObject *scheme = t->scheme;
    uint8_t *cp = (uint8_t *)PyByteArray_AS_STRING(codes);
    Py_ssize_t ncodes = PyByteArray_GET_SIZE(codes);
    Py_ssize_t n_refs = 0;
    for (Py_ssize_t w = 0; w < PyList_GET_SIZE(works); w++) {
        if ((w & 255) == 255) {
            Py_BEGIN_ALLOW_THREADS
            Py_END_ALLOW_THREADS
        }
        PyObject *work = PyList_GET_ITEM(works, w);
        Py_ssize_t tx = PyLong_AsSsize_t(PyTuple_GET_ITEM(work, 0));
        long txtype = PyLong_AsLong(PyTuple_GET_ITEM(work, 1));
        Py_ssize_t cslot = PyLong_AsSsize_t(PyTuple_GET_ITEM(work, 2));
        if (tx < 0 || tx >= ncodes || cslot < 0
            || cslot >= PyList_GET_SIZE(c_ents)) {
            PyErr_SetString(PyExc_IndexError, "assemble(): slot range");
            return -1;
        }
        PyObject *ent = PyList_GET_ITEM(c_ents, cslot);
        if (ent == Py_None) {         /* MSP rejected the creator */
            cp[tx] = VC_BAD_CREATOR;
            continue;
        }
        PyObject *creator = PyTuple_GET_ITEM(ent, 0);
        PyObject *wire = PyTuple_GET_ITEM(ent, 1);
        PyObject *csig = PyTuple_GET_ITEM(work, 5);
        Py_ssize_t cidx;
        int32_t kid = slot_key(t, ckey, cslot, ent);
        if (kid == KEY_ERR)
            return -1;
        if (kid >= 0 && row_shaped(csig, PyTuple_GET_ITEM(work, 4))) {
            cidx = table_add(t, kid, csig, PyTuple_GET_ITEM(work, 4));
        } else if (wire != Py_None) {
            PyObject *cscheme = PyTuple_GET_ITEM(ent, 2);
            int digested = PyObject_RichCompareBool(cscheme, scheme, Py_EQ);
            if (digested < 0)
                return -1;
            cidx = table_add_fields(t, cscheme, wire, csig,
                                    PyTuple_GET_ITEM(work, digested
                                                     ? 4       /* pdigest */
                                                     : 3));    /* payload */
        } else {
            PyObject *item = PyObject_CallMethodObjArgs(
                creator, s_verify_item, PyTuple_GET_ITEM(work, 3), csig,
                NULL);
            if (!item)
                return -1;
            cidx = table_add_item(t, item);
            Py_DECREF(item);
        }
        if (cidx < 0)
            return -1;
        PyObject *entries = PyList_New(0);
        if (!entries)
            return -1;
        int dead = 0;
        PyObject *acts = PyTuple_GET_ITEM(work, 6);
        if (txtype != 0 && acts != Py_None) {
            for (Py_ssize_t a = 0;
                 !dead && a < PyList_GET_SIZE(acts); a++) {
                PyObject *act = PyList_GET_ITEM(acts, a);
                PyObject *endorsed = PyTuple_GET_ITEM(act, 1);
                PyObject *ends2 = PyTuple_GET_ITEM(act, 2);
                PyObject *ns_names = PyTuple_GET_ITEM(act, 3);
                PyObject *sigset = PyList_New(0);
                if (!sigset) { Py_DECREF(entries); return -1; }
                for (Py_ssize_t e = 0; e < PyList_GET_SIZE(ends2); e++) {
                    PyObject *end3 = PyList_GET_ITEM(ends2, e);
                    Py_ssize_t slot =
                        PyLong_AsSsize_t(PyTuple_GET_ITEM(end3, 0));
                    if (slot < 0 || slot >= PyList_GET_SIZE(e_ents)) {
                        PyErr_SetString(PyExc_IndexError,
                                        "assemble(): endorser slot");
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                    PyObject *eent = PyList_GET_ITEM(e_ents, slot);
                    if (eent == Py_None)   /* undeserializable: skip */
                        continue;
                    PyObject *ident = PyTuple_GET_ITEM(eent, 0);
                    PyObject *ewire = PyTuple_GET_ITEM(eent, 1);
                    PyObject *esig = PyTuple_GET_ITEM(end3, 1);
                    Py_ssize_t eidx;
                    int32_t ekid = slot_key(t, ekey, slot, eent);
                    if (ekid == KEY_ERR) {
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                    PyObject *escheme =
                        ewire != Py_None ? PyTuple_GET_ITEM(eent, 2) : NULL;
                    int digested = ekid >= 0;
                    if (!digested && escheme) {
                        digested = PyObject_RichCompareBool(escheme, scheme,
                                                            Py_EQ);
                        if (digested < 0) {
                            Py_DECREF(sigset); Py_DECREF(entries);
                            return -1;
                        }
                    }
                    if (ekid >= 0
                        && row_shaped(esig, PyTuple_GET_ITEM(end3, 2))) {
                        eidx = table_add(t, ekid, esig,
                                         PyTuple_GET_ITEM(end3, 2));
                    } else if (digested) {
                        eidx = table_add_fields(t, escheme, ewire, esig,
                                                PyTuple_GET_ITEM(end3, 2));
                    } else {      /* over the message itself */
                        PyObject *msg = PySequence_Concat(
                            endorsed, PyList_GET_ITEM(endorsers, slot));
                        if (!msg) {
                            Py_DECREF(sigset); Py_DECREF(entries);
                            return -1;
                        }
                        if (escheme) {
                            eidx = table_add_fields(t, escheme, ewire, esig,
                                                    msg);
                            Py_DECREF(msg);
                        } else {
                            PyObject *item = PyObject_CallMethodObjArgs(
                                ident, s_verify_item, msg, esig, NULL);
                            Py_DECREF(msg);
                            if (!item) {
                                Py_DECREF(sigset); Py_DECREF(entries);
                                return -1;
                            }
                            eidx = table_add_item(t, item);
                            Py_DECREF(item);
                        }
                    }
                    if (eidx < 0) {
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                    PyObject *eio = PyLong_FromSsize_t(eidx);
                    PyObject *pair = eio ? PyTuple_New(2) : NULL;
                    if (!pair) {
                        Py_XDECREF(eio);
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                    PyTuple_SET_ITEM(pair, 0, eio);
                    Py_INCREF(ident);
                    PyTuple_SET_ITEM(pair, 1, ident);
                    int rc = PyList_Append(sigset, pair);
                    Py_DECREF(pair);
                    if (rc < 0) {
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                }
                for (Py_ssize_t s = 0; s < PyList_GET_SIZE(ns_names);
                     s++) {
                    PyObject *ns = PyList_GET_ITEM(ns_names, s);
                    PyObject *pol =
                        PyDict_GetItemWithError(pol_cache, ns);
                    if (!pol) {
                        if (PyErr_Occurred()) {
                            Py_DECREF(sigset); Py_DECREF(entries);
                            return -1;
                        }
                        pol = PyObject_CallFunctionObjArgs(policy_for,
                                                           ns, NULL);
                        if (!pol || PyDict_SetItem(pol_cache, ns,
                                                   pol) < 0) {
                            Py_XDECREF(pol);
                            Py_DECREF(sigset); Py_DECREF(entries);
                            return -1;
                        }
                        Py_DECREF(pol);   /* pol_cache holds it */
                    }
                    if (pol == Py_None) {  /* unknown namespace */
                        cp[tx] = VC_INVALID_CC;
                        dead = 1;
                        break;
                    }
                    PyObject *entry = PyTuple_New(2);
                    if (!entry) {
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                    Py_INCREF(pol);
                    PyTuple_SET_ITEM(entry, 0, pol);
                    Py_INCREF(sigset);
                    PyTuple_SET_ITEM(entry, 1, sigset);
                    int rc = PyList_Append(entries, entry);
                    Py_DECREF(entry);
                    if (rc < 0) {
                        Py_DECREF(sigset); Py_DECREF(entries);
                        return -1;
                    }
                }
                Py_DECREF(sigset);
            }
        }
        if (dead) {
            Py_DECREF(entries);
            continue;
        }
        n_refs += 1;
        for (Py_ssize_t s = 0; s < PyList_GET_SIZE(entries); s++)
            n_refs += PyList_GET_SIZE(
                PyTuple_GET_ITEM(PyList_GET_ITEM(entries, s), 1));
        PyObject *plan = PyTuple_New(3);
        PyObject *cio = PyLong_FromSsize_t(cidx);
        if (!plan || !cio) {
            Py_XDECREF(plan); Py_XDECREF(cio); Py_DECREF(entries);
            return -1;
        }
        Py_INCREF(PyTuple_GET_ITEM(work, 0));
        PyTuple_SET_ITEM(plan, 0, PyTuple_GET_ITEM(work, 0));
        PyTuple_SET_ITEM(plan, 1, cio);
        PyTuple_SET_ITEM(plan, 2, entries);
        int rc = PyList_Append(plans, plan);
        Py_DECREF(plan);
        if (rc < 0)
            return -1;
    }
    return n_refs;
}

/* assemble(works, c_ents, e_ents, endorsers, codes, plans,
 *          verify_item_cls, scheme_p256, policy_for, pol_cache)
 *   -> (table: SigTable, n_refs)
 *
 * c_ents/e_ents: per-slot (identity, pub_wire|None, scheme|None) or
 * None for identities the MSP rejected.  With a pub_wire the item is
 * its four plain fields: under `scheme_p256` it is over the walker's
 * SHA-256 digest and becomes a ROW of the table — no VerifyItem, no
 * container, nothing called in Python; under another scheme it is over
 * the message itself (Ed25519 signs the message: payload for a
 * creator, endorsed || endorser for an endorsement) and is interned in
 * the table's short list of VerifyItems.  Without one the identity is
 * asked (`verify_item`: idemix) and its item goes to that list too.
 * Appends to `plans`
 * (tx_num, creator_idx, [(policy, [(item_idx, identity)...])...]),
 * the indices being dispatch positions over rows and list together, in
 * EXACTLY the Python tail's order:
 * creator first, then each action's endorsements, then that action's
 * namespace policy lookups (a missing policy kills the tx but keeps
 * already-interned items — n_unique_items parity).  n_refs counts
 * 1 + sigset size per namespace entry over SURVIVING works only,
 * matching _finish_inner's accounting. */
static PyObject *py_assemble(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *works, *c_ents, *e_ents, *endorsers, *codes, *plans, *cls,
             *scheme, *policy_for, *pol_cache;
    if (!PyArg_ParseTuple(args, "OOOOOOOOOO", &works, &c_ents, &e_ents,
                          &endorsers, &codes, &plans, &cls, &scheme,
                          &policy_for, &pol_cache))
        return NULL;
    if (!PyList_Check(works) || !PyList_Check(c_ents)
        || !PyList_Check(e_ents) || !PyList_Check(endorsers)
        || !PyByteArray_Check(codes) || !PyList_Check(plans)
        || !PyDict_Check(pol_cache)) {
        PyErr_SetString(PyExc_TypeError, "assemble(): bad argument types");
        return NULL;
    }
    /* room for every signature the works carry: one a creator, one an
     * endorsement kept by digest's per-action dedup */
    Py_ssize_t cap = 0;
    for (Py_ssize_t w = 0; w < PyList_GET_SIZE(works); w++) {
        PyObject *work = PyList_GET_ITEM(works, w);
        if (!PyTuple_Check(work) || PyTuple_GET_SIZE(work) != 7) {
            PyErr_SetString(PyExc_TypeError, "assemble(): bad work");
            return NULL;
        }
        PyObject *acts = PyTuple_GET_ITEM(work, 6);
        cap += 1;
        for (Py_ssize_t a = 0;
             acts != Py_None && a < PyList_GET_SIZE(acts); a++)
            cap += PyList_GET_SIZE(
                PyTuple_GET_ITEM(PyList_GET_ITEM(acts, a), 2));
    }
    Py_ssize_t nc = PyList_GET_SIZE(c_ents), ne = PyList_GET_SIZE(e_ents);
    SigTable *t = table_new(cap, cls, scheme);
    int32_t *slots = PyMem_Malloc((size_t)(nc + ne + 1) * sizeof(int32_t));
    PyObject *ret = NULL;
    if (t && slots) {
        for (Py_ssize_t i = 0; i < nc + ne; i++)
            slots[i] = KEY_UNSET;
        Py_ssize_t n_refs = assemble_works(
            t, slots, slots + nc, works, c_ents, e_ents, endorsers, codes,
            plans, policy_for, pol_cache);
        if (n_refs >= 0 && table_seal(t) == 0)
            ret = Py_BuildValue("(On)", (PyObject *)t, n_refs);
    } else if (t) {
        PyErr_NoMemory();
    }
    PyMem_Free(slots);
    Py_XDECREF(t);
    return ret;
}

/* gate(plans, verdict: buffer[u8], codes, plugin, evaluator, eval_cache)
 *
 * Folds the device verdict bitmap into final ValidationCodes without a
 * per-tx Python loop.  Per plan: creator bit (miss -> BAD_CREATOR_SIG),
 * then per (policy, sigset) the verdict-filtered valid-identity list is
 * evaluated via `plugin` memoized in eval_cache keyed
 * (id(policy), id(ident)...) — same purity argument as
 * txvalidator._memoized_plugin (policies and identities are interned
 * per block, so ids are stable).  Any falsy evaluation ->
 * ENDORSEMENT_POLICY_FAILURE, else VALID. */
static PyObject *py_gate(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *plans, *codes, *plugin, *evaluator, *eval_cache;
    Py_buffer vb;
    if (!PyArg_ParseTuple(args, "Oy*OOOO", &plans, &vb, &codes, &plugin,
                          &evaluator, &eval_cache))
        return NULL;
    if (!PyList_Check(plans) || !PyByteArray_Check(codes)
        || !PyDict_Check(eval_cache)) {
        PyBuffer_Release(&vb);
        PyErr_SetString(PyExc_TypeError, "gate(): bad argument types");
        return NULL;
    }
    const uint8_t *v = (const uint8_t *)vb.buf;
    Py_ssize_t nv = vb.len;
    uint8_t *cp = (uint8_t *)PyByteArray_AS_STRING(codes);
    Py_ssize_t ncodes = PyByteArray_GET_SIZE(codes);
    for (Py_ssize_t p = 0; p < PyList_GET_SIZE(plans); p++) {
        if ((p & 255) == 255) {
            Py_BEGIN_ALLOW_THREADS
            Py_END_ALLOW_THREADS
        }
        PyObject *plan = PyList_GET_ITEM(plans, p);
        Py_ssize_t tx = PyLong_AsSsize_t(PyTuple_GET_ITEM(plan, 0));
        Py_ssize_t cidx = PyLong_AsSsize_t(PyTuple_GET_ITEM(plan, 1));
        if (tx < 0 || tx >= ncodes)
            goto typefail;
        if (cidx < 0 || cidx >= nv || !v[cidx]) {
            cp[tx] = VC_BAD_CREATOR;
            continue;
        }
        PyObject *entries = PyTuple_GET_ITEM(plan, 2);
        int failed = 0;
        for (Py_ssize_t s = 0;
             !failed && s < PyList_GET_SIZE(entries); s++) {
            PyObject *entry = PyList_GET_ITEM(entries, s);
            PyObject *pol = PyTuple_GET_ITEM(entry, 0);
            PyObject *sigset = PyTuple_GET_ITEM(entry, 1);
            Py_ssize_t m = PyList_GET_SIZE(sigset);
            PyObject *valid = PyList_New(0);
            if (!valid)
                goto fail;
            for (Py_ssize_t e = 0; e < m; e++) {
                PyObject *pair = PyList_GET_ITEM(sigset, e);
                Py_ssize_t idx =
                    PyLong_AsSsize_t(PyTuple_GET_ITEM(pair, 0));
                if (idx >= 0 && idx < nv && v[idx]
                    && PyList_Append(valid,
                                     PyTuple_GET_ITEM(pair, 1)) < 0) {
                    Py_DECREF(valid);
                    goto fail;
                }
            }
            Py_ssize_t nvalid = PyList_GET_SIZE(valid);
            PyObject *key = PyTuple_New(1 + nvalid);
            if (!key) { Py_DECREF(valid); goto fail; }
            PyObject *ko = PyLong_FromVoidPtr((void *)pol);
            if (!ko) { Py_DECREF(key); Py_DECREF(valid); goto fail; }
            PyTuple_SET_ITEM(key, 0, ko);
            int keyfail = 0;
            for (Py_ssize_t e = 0; e < nvalid; e++) {
                ko = PyLong_FromVoidPtr(
                    (void *)PyList_GET_ITEM(valid, e));
                if (!ko) { keyfail = 1; break; }
                PyTuple_SET_ITEM(key, 1 + e, ko);
            }
            if (keyfail) {
                Py_DECREF(key); Py_DECREF(valid);
                goto fail;
            }
            PyObject *r = PyDict_GetItemWithError(eval_cache, key);
            int truth;
            if (r) {
                truth = PyObject_IsTrue(r);
            } else {
                if (PyErr_Occurred()) {
                    Py_DECREF(key); Py_DECREF(valid);
                    goto fail;
                }
                PyObject *r2 = PyObject_CallFunctionObjArgs(
                    plugin, pol, valid, evaluator, NULL);
                if (!r2 || PyDict_SetItem(eval_cache, key, r2) < 0) {
                    Py_XDECREF(r2); Py_DECREF(key); Py_DECREF(valid);
                    goto fail;
                }
                truth = PyObject_IsTrue(r2);
                Py_DECREF(r2);
            }
            Py_DECREF(key);
            Py_DECREF(valid);
            if (truth < 0)
                goto fail;
            if (!truth) {
                cp[tx] = VC_POLICY_FAIL;
                failed = 1;
            }
        }
        if (!failed)
            cp[tx] = VC_VALID;
    }
    PyBuffer_Release(&vb);
    Py_RETURN_NONE;
typefail:
    PyErr_SetString(PyExc_IndexError, "gate(): tx out of range");
fail:
    PyBuffer_Release(&vb);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* Batched strict-DER ECDSA signature parsing.
 *
 * The provider's P-256 pass parses every signature's DER SEQUENCE of
 * two INTEGERs before packing; one C call over the whole batch replaces
 * ~1.6 us/sig of per-item Python (bccsp/jaxtpu._parse_p256's
 * decode_dss_signature loop) with ~40 ns/sig.  Semantics mirror the
 * Python path exactly: strict DER (minimal lengths, minimal integer
 * encoding — what cryptography's decode_dss_signature enforces) AND the
 * range gate 0 < r,s < 2^256; any failure clears the ok flag (the
 * caller host-rejects, verdict stays False).
 *
 * parse_der_sigs(sigs: sequence[bytes]) -> (ok: bytes[N], rs: bytes[64N])
 *   rs holds r32be || s32be per signature (zero-padded on the left).
 */

/* one strict-DER unsigned INTEGER in (0, 2^256) -> 32B big-endian */
static int der_int32(const uint8_t **pp, const uint8_t *end, uint8_t out[32])
{
    const uint8_t *p = *pp;
    if (end - p < 2 || p[0] != 0x02) return -1;
    uint32_t l = p[1];
    /* values < 2^256 encode in <= 33 bytes < 128: short form only */
    if (l == 0 || l > 33 || (uint32_t)(end - p - 2) < l) return -1;
    p += 2;
    if (p[0] & 0x80) return -1;                 /* negative: out of range */
    if (l > 1 && p[0] == 0 && !(p[1] & 0x80)) return -1;   /* non-minimal */
    if (l == 33 && p[0] != 0) return -1;        /* >= 2^256 */
    const uint8_t *v = p;
    uint32_t vn = l;
    if (l == 33) { v++; vn = 32; }
    int zero = 1;
    for (uint32_t i = 0; i < vn; i++)
        if (v[i]) { zero = 0; break; }
    if (zero) return -1;                        /* r/s must be nonzero */
    memset(out, 0, 32 - vn);
    memcpy(out + (32 - vn), v, vn);
    *pp = p + l;
    return 0;
}

/* one signature: strict-DER SEQUENCE of two such INTEGERs and nothing
 * else -> 1 with r32be || s32be in out, 0 with out zeroed */
static int der_sig64(const uint8_t *p, Py_ssize_t sn, uint8_t out[64])
{
    const uint8_t *end = p + sn;
    /* SEQUENCE header, short-form length covering the whole rest */
    if (sn >= 8 && p[0] == 0x30 && p[1] < 0x80
        && (Py_ssize_t)p[1] == sn - 2) {
        p += 2;
        if (der_int32(&p, end, out) == 0
            && der_int32(&p, end, out + 32) == 0
            && p == end)                 /* no trailing bytes */
            return 1;
    }
    memset(out, 0, 64);
    return 0;
}

static PyObject *py_parse_der_sigs(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *sigs;
    if (!PyArg_ParseTuple(args, "O", &sigs))
        return NULL;
    PyObject *seq = PySequence_Fast(sigs, "parse_der_sigs needs a sequence");
    if (!seq)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    PyObject *ok_b = PyBytes_FromStringAndSize(NULL, n);
    PyObject *rs_b = PyBytes_FromStringAndSize(NULL, n * 64);
    if (!ok_b || !rs_b) {
        Py_XDECREF(ok_b); Py_XDECREF(rs_b); Py_DECREF(seq);
        return NULL;
    }
    uint8_t *ok = (uint8_t *)PyBytes_AS_STRING(ok_b);
    uint8_t *rs = (uint8_t *)PyBytes_AS_STRING(rs_b);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *sig = PySequence_Fast_GET_ITEM(seq, i);
        char *cp;
        Py_ssize_t sn;
        if (PyBytes_AsStringAndSize(sig, &cp, &sn) < 0) {
            PyErr_Clear();               /* non-bytes: host reject */
            memset(rs + i * 64, 0, 64);
            ok[i] = 0;
            continue;
        }
        ok[i] = (uint8_t)der_sig64((const uint8_t *)cp, sn, rs + i * 64);
    }
    Py_DECREF(seq);
    PyObject *out = Py_BuildValue("(NN)", ok_b, rs_b);
    if (!out) { Py_DECREF(ok_b); Py_DECREF(rs_b); }
    return out;
}

static PyObject *py_sha256(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer buf;
    if (!PyArg_ParseTuple(args, "y*", &buf))
        return NULL;
    uint8_t out[32];
    sha256_oneshot(buf.buf, buf.len, out);
    PyBuffer_Release(&buf);
    return PyBytes_FromStringAndSize((const char *)out, 32);
}

static PyMethodDef methods[] = {
    {"collect", py_collect, METH_VARARGS,
     "collect(envs, channel_id) -> per-tx structural results"},
    {"digest", py_digest, METH_VARARGS,
     "digest(envs, channel_id, carry, oracle) -> "
     "(codes, seen, works, creators, endorsers)"},
    {"digest_spans", py_digest_spans, METH_VARARGS,
     "digest_spans(base, spans, channel_id, carry, oracle) -> "
     "digest() over zero-copy (u64 off, u64 len) spans into base"},
    {"assemble", py_assemble, METH_VARARGS,
     "assemble(works, c_ents, e_ents, endorsers, codes, plans, "
     "verify_item_cls, scheme_p256, policy_for, pol_cache) -> "
     "(SigTable, n_refs)"},
    {"pack_items", py_pack_items, METH_VARARGS,
     "pack_items(items, verify_item_cls, scheme_p256) -> SigTable"},
    {"gate", py_gate, METH_VARARGS,
     "gate(plans, verdict, codes, plugin, evaluator, eval_cache)"},
    {"parse_der_sigs", py_parse_der_sigs, METH_VARARGS,
     "parse_der_sigs(sigs) -> (ok bytes, r32s32 bytes)"},
    {"sha256", py_sha256, METH_VARARGS, "sha256(data) -> 32-byte digest"},
    {NULL, NULL, 0, NULL}};

static struct PyModuleDef moddef = {
    PyModuleDef_HEAD_INIT, "_fastcollect",
    "C pass-1 block collection (txvalidator hot path)", -1, methods,
    NULL, NULL, NULL, NULL};

PyMODINIT_FUNC PyInit__fastcollect(void)
{
#ifdef HAVE_X86
    unsigned eax, ebx, ecx, edx;
    if (__get_cpuid_count(7, 0, &eax, &ebx, &ecx, &edx) && (ebx & (1u << 29)))
        sha256_block = sha256_block_shani;
#endif
    s_verify_item = PyUnicode_InternFromString("verify_item");
    if (!s_verify_item || PyType_Ready(&SigTableType) < 0)
        return NULL;
    PyObject *m = PyModule_Create(&moddef);
    if (m) {
        Py_INCREF(&SigTableType);
        if (PyModule_AddObject(m, "SigTable",
                               (PyObject *)&SigTableType) < 0) {
            Py_DECREF(&SigTableType);
            Py_DECREF(m);
            return NULL;
        }
    }
    return m;
}
