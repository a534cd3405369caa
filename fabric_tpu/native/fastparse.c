/* Zero-copy wire ingest — native block/envelope span parser.
 *
 * fastcollect.c took over txvalidator pass 1 *after* Python had already
 * decoded the block container and materialized a list of per-envelope
 * bytes objects.  This module moves the C plane one layer up, to the
 * wire: it takes the raw FTLV frame bytes (fabric_tpu/utils/serde.py
 * format) of a whole Block or a single Envelope and extracts the byte
 * SPANS the rest of the pipeline needs — without creating any per-tx
 * Python object.  The envelope span table is written into an
 * arena-allocated, ring-pooled buffer so steady-state block ingest does
 * not call malloc at all.
 *
 * Exported:
 *   parse_block(buf) -> (number, previous_hash, data_hash,
 *                        data_off, data_end, n, spans, meta_val_off)
 *                       | None
 *     buf must be EXACTLY the canonical encoding of
 *       {"data": [bytes, ...], "header": {"data_hash": bytes,
 *        "number": i64, "previous_hash": bytes}, "metadata": {...}}
 *     (strict canonical form throughout: sorted unique dict keys,
 *     minimal 'V' ints, valid UTF-8, nesting <= MAX_DEPTH, no trailing
 *     bytes — the same rules serde.decode enforces).  Anything else
 *     returns None and the caller falls back to Block.deserialize, so
 *     accept/reject behavior of the system never changes — only who
 *     does the work.
 *       spans        arena buffer of n (u64 off, u64 len) native-endian
 *                    pairs: block.data[i] == buf[off:off+len]
 *       data_off/end span of the whole data LIST value, so
 *                    sha256(buf[data_off:data_end]) ==
 *                    block_data_hash(block.data) bit-identically
 *       meta_val_off offset where the metadata VALUE begins; because
 *                    "metadata" is the last key of the sorted top dict,
 *                    buf[:meta_val_off] + serde.encode(metadata_dict)
 *                    re-serializes a metadata-mutated block by splice
 *   envelope_summary(buf) -> (type, channel_id, txid) | None
 *     the gateway submit path's header peek: what
 *     Envelope.deserialize(buf).header().channel_header would yield,
 *     without building the Envelope/Header object trees.  None on any
 *     deviation from the strict shape (caller falls back).
 *   stats() -> dict of arena-pool and accept/reject counters
 *
 * Arena lifecycle: parse_block writes the span table into an Arena
 * object (read-only buffer protocol).  When the Arena's refcount drops
 * to zero its backing buffer is pushed onto a small ring free-list
 * (FP_POOL entries) and the next parse_block reuses it; only pool
 * overflow frees.  All pool operations run under the GIL (parse holds
 * it throughout; tp_dealloc always has it), so no extra locking.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>
#include <string.h>

/* ------------------------------------------------------------------ */
/* FTLV cursor (format: fabric_tpu/utils/serde.py; walker idiom shared
 * with native/fastcollect.c — the two must enforce identical rules)    */

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

#define MAX_DEPTH 64

/* strict UTF-8 (CPython decoder semantics: no overlongs, no
 * surrogates, max U+10FFFF) */
static int utf8_ok(const uint8_t *p, uint32_t n)
{
    uint32_t i = 0;
    while (i < n) {
        uint8_t b = p[i];
        if (b < 0x80) { i++; continue; }
        if (b < 0xC2) return 0;
        if (b < 0xE0) {
            if (n - i < 2 || (p[i+1] & 0xC0) != 0x80) return 0;
            i += 2; continue;
        }
        if (b < 0xF0) {
            if (n - i < 3) return 0;
            uint8_t b1 = p[i+1], b2 = p[i+2];
            if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80) return 0;
            if (b == 0xE0 && b1 < 0xA0) return 0;
            if (b == 0xED && b1 >= 0xA0) return 0;
            i += 3; continue;
        }
        if (b < 0xF5) {
            if (n - i < 4) return 0;
            uint8_t b1 = p[i+1], b2 = p[i+2], b3 = p[i+3];
            if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80
                || (b3 & 0xC0) != 0x80) return 0;
            if (b == 0xF0 && b1 < 0x90) return 0;
            if (b == 0xF4 && b1 >= 0x90) return 0;
            i += 4; continue;
        }
        return 0;
    }
    return 1;
}

/* validate one value in strict canonical form (serde.decode rules) */
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

/* Enter a dict ('D'): entry count out, -1 if not a dict header. */
static int dict_enter(cur_t *c, uint32_t *count)
{
    if (c->p >= c->end || *c->p != 'D') return -1;
    c->p++;
    return rd_u32(c, count);
}

/* Next dict entry's key span (must be valid UTF-8 and strictly greater
 * than *prev — the canonical-order check other walkers do inline). */
static int dict_key(cur_t *c, const uint8_t **prev, uint32_t *prev_n,
                    const uint8_t **key, uint32_t *klen)
{
    if (rd_u32(c, klen) < 0 || (uint32_t)(c->end - c->p) < *klen) return -1;
    *key = c->p;
    c->p += *klen;
    if (!utf8_ok(*key, *klen)) return -1;
    if (*prev) {
        uint32_t m = *prev_n < *klen ? *prev_n : *klen;
        int cmp = memcmp(*prev, *key, m);
        if (cmp > 0 || (cmp == 0 && *prev_n >= *klen)) return -1;
    }
    *prev = *key;
    *prev_n = *klen;
    return 0;
}

static int key_is(const uint8_t *key, uint32_t klen, const char *name)
{
    size_t n = strlen(name);
    return klen == n && memcmp(key, name, n) == 0;
}

/* read a 'B' (bytes) value's content span */
static int rd_bytes(cur_t *c, const uint8_t **p, uint32_t *n)
{
    if (c->p >= c->end || *c->p != 'B') return -1;
    c->p++;
    if (rd_u32(c, n) < 0 || (uint32_t)(c->end - c->p) < *n) return -1;
    *p = c->p;
    c->p += *n;
    return 0;
}

/* read an 'S' (str) value's content span (UTF-8 validated) */
static int rd_str(cur_t *c, const uint8_t **p, uint32_t *n)
{
    if (c->p >= c->end || *c->p != 'S') return -1;
    c->p++;
    if (rd_u32(c, n) < 0 || (uint32_t)(c->end - c->p) < *n) return -1;
    if (!utf8_ok(c->p, *n)) return -1;
    *p = c->p;
    c->p += *n;
    return 0;
}

/* read an 'I' (fixed i64) value */
static int rd_i64(cur_t *c, int64_t *out)
{
    if (c->p >= c->end || *c->p != 'I') return -1;
    c->p++;
    if (c->end - c->p < 8) return -1;
    uint64_t v = 0;
    for (int i = 0; i < 8; i++)
        v = (v << 8) | c->p[i];
    c->p += 8;
    *out = (int64_t)v;
    return 0;
}

/* ------------------------------------------------------------------ */
/* Arena: ring-pooled span buffer with read-only buffer protocol       */

#define FP_POOL 8

static struct { uint8_t *buf; size_t cap; } pool[FP_POOL];
static int pool_n = 0;

static uint64_t st_pool_hit = 0;    /* acquires served from the pool   */
static uint64_t st_pool_miss = 0;   /* acquires that hit malloc        */
static uint64_t st_pool_drop = 0;   /* releases freed (pool full)      */
static uint64_t st_blk_accept = 0;
static uint64_t st_blk_reject = 0;
static uint64_t st_env_accept = 0;
static uint64_t st_env_reject = 0;

typedef struct {
    PyObject_HEAD
    uint8_t *buf;
    size_t cap;
    Py_ssize_t len;
} FPArena;

static void arena_dealloc(PyObject *self)
{
    FPArena *a = (FPArena *)self;
    if (a->buf) {
        if (pool_n < FP_POOL) {
            pool[pool_n].buf = a->buf;
            pool[pool_n].cap = a->cap;
            pool_n++;
        } else {
            st_pool_drop++;
            PyMem_RawFree(a->buf);
        }
        a->buf = NULL;
    }
    Py_TYPE(self)->tp_free(self);
}

static int arena_getbuffer(PyObject *self, Py_buffer *view, int flags)
{
    FPArena *a = (FPArena *)self;
    return PyBuffer_FillInfo(view, self, a->buf, a->len, 1, flags);
}

static PyBufferProcs arena_as_buffer = {
    arena_getbuffer,
    NULL,
};

static Py_ssize_t arena_length(PyObject *self)
{
    return ((FPArena *)self)->len;
}

static PySequenceMethods arena_as_sequence = {
    .sq_length = arena_length,
};

static PyTypeObject FPArenaType = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_fastparse.Arena",
    .tp_basicsize = sizeof(FPArena),
    .tp_dealloc = arena_dealloc,
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_doc = "ring-pooled read-only span buffer",
    .tp_as_buffer = &arena_as_buffer,
    .tp_as_sequence = &arena_as_sequence,
    .tp_new = NULL,                 /* not constructible from Python */
};

/* round up to the next power of two, >= 256 */
static size_t round_cap(size_t need)
{
    size_t cap = 256;
    while (cap < need)
        cap <<= 1;
    return cap;
}

static FPArena *arena_acquire(size_t need)
{
    uint8_t *buf = NULL;
    size_t cap = 0;
    for (int i = 0; i < pool_n; i++) {
        if (pool[i].cap >= need) {
            buf = pool[i].buf;
            cap = pool[i].cap;
            pool_n--;
            pool[i] = pool[pool_n];
            st_pool_hit++;
            break;
        }
    }
    if (!buf) {
        cap = round_cap(need);
        buf = PyMem_RawMalloc(cap);
        if (!buf) {
            PyErr_NoMemory();
            return NULL;
        }
        st_pool_miss++;
    }
    FPArena *a = PyObject_New(FPArena, &FPArenaType);
    if (!a) {
        /* return the buffer to the pool rather than leak/free churn */
        if (pool_n < FP_POOL) {
            pool[pool_n].buf = buf;
            pool[pool_n].cap = cap;
            pool_n++;
        } else {
            PyMem_RawFree(buf);
        }
        return NULL;
    }
    a->buf = buf;
    a->cap = cap;
    a->len = 0;
    return a;
}

/* ------------------------------------------------------------------ */
/* parse_block                                                         */

static PyObject *py_parse_block(PyObject *self, PyObject *arg)
{
    (void)self;
    Py_buffer in;
    if (PyObject_GetBuffer(arg, &in, PyBUF_CONTIG_RO) < 0)
        return NULL;
    const uint8_t *base = in.buf;
    cur_t c = {base, base + in.len};

    int64_t number = 0;
    const uint8_t *prev_p = NULL, *dhash_p = NULL;
    uint32_t prev_n = 0, dhash_n = 0;
    size_t data_off = 0, data_end = 0, meta_off = 0;
    uint32_t ndata = 0;
    FPArena *spans = NULL;

    uint32_t top_n;
    if (dict_enter(&c, &top_n) < 0 || top_n != 3)
        goto reject;

    /* --- "data": [bytes, ...] ---------------------------------------- */
    {
        const uint8_t *k; uint32_t kn;
        const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
        if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0
            || !key_is(k, kn, "data"))
            goto reject;
        if (c.p >= c.end || *c.p != 'L')
            goto reject;
        data_off = (size_t)(c.p - base);
        c.p++;
        if (rd_u32(&c, &ndata) < 0)
            goto reject;
        /* a genuine n-item list needs >= 5 bytes per 'B' item; a count
         * this buffer cannot possibly hold would otherwise make us
         * malloc a huge span table before the walk fails */
        if ((size_t)ndata > (size_t)in.len / 5)
            goto reject;
        spans = arena_acquire(ndata ? (size_t)ndata * 16 : 16);
        if (!spans)
            goto error;
        uint64_t *tab = (uint64_t *)spans->buf;
        for (uint32_t i = 0; i < ndata; i++) {
            const uint8_t *bp; uint32_t bn;
            if (rd_bytes(&c, &bp, &bn) < 0)
                goto reject;
            tab[2 * i] = (uint64_t)(bp - base);
            tab[2 * i + 1] = bn;
        }
        spans->len = (Py_ssize_t)ndata * 16;
        data_end = (size_t)(c.p - base);
    }

    /* --- "header": {data_hash, number, previous_hash} ----------------- */
    {
        const uint8_t *k; uint32_t kn;
        const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
        if (rd_u32(&c, &kn) < 0 || (uint32_t)(c.end - c.p) < kn)
            goto reject;
        k = c.p;
        c.p += kn;
        if (!key_is(k, kn, "header"))
            goto reject;
        uint32_t hn;
        if (dict_enter(&c, &hn) < 0 || hn != 3)
            goto reject;
        if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0
            || !key_is(k, kn, "data_hash")
            || rd_bytes(&c, &dhash_p, &dhash_n) < 0)
            goto reject;
        if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0
            || !key_is(k, kn, "number")
            || rd_i64(&c, &number) < 0)
            goto reject;
        if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0
            || !key_is(k, kn, "previous_hash")
            || rd_bytes(&c, &prev_p, &prev_n) < 0)
            goto reject;
    }

    /* --- "metadata": any canonical dict, last value in the buffer ----- */
    {
        const uint8_t *k; uint32_t kn;
        if (rd_u32(&c, &kn) < 0 || (uint32_t)(c.end - c.p) < kn)
            goto reject;
        k = c.p;
        c.p += kn;
        if (!key_is(k, kn, "metadata"))
            goto reject;
        meta_off = (size_t)(c.p - base);
        if (c.p >= c.end || *c.p != 'D')
            goto reject;
        if (canon_value_d(&c, 1) < 0)
            goto reject;
        if (c.p != c.end)
            goto reject;
    }

    {
        PyObject *res = Py_BuildValue(
            "(Ly#y#nnIOn)",
            (long long)number,
            (const char *)prev_p, (Py_ssize_t)prev_n,
            (const char *)dhash_p, (Py_ssize_t)dhash_n,
            (Py_ssize_t)data_off, (Py_ssize_t)data_end,
            (unsigned int)ndata,
            (PyObject *)spans,
            (Py_ssize_t)meta_off);
        Py_DECREF(spans);
        PyBuffer_Release(&in);
        if (res)
            st_blk_accept++;
        return res;
    }

reject:
    Py_XDECREF(spans);
    PyBuffer_Release(&in);
    st_blk_reject++;
    Py_RETURN_NONE;
error:
    Py_XDECREF(spans);
    PyBuffer_Release(&in);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* envelope_summary                                                    */

/* Walk a strict-canonical dict; for the single entry whose key matches
 * `want`, leave a sub-cursor positioned at its value and fully
 * canon-validate every other entry.  Returns 1 found / 0 not found /
 * -1 malformed.  The full dict (including the wanted value) is
 * canonically validated either way. */
static int dict_find(cur_t *c, const char *want, cur_t *val)
{
    uint32_t n;
    int found = 0;
    if (dict_enter(c, &n) < 0) return -1;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return -1;
        const uint8_t *vstart = c->p;
        if (canon_value_d(c, 1) < 0) return -1;
        if (key_is(k, kn, want)) {
            val->p = vstart;
            val->end = c->p;
            found = 1;
        }
    }
    return found;
}

static PyObject *py_envelope_summary(PyObject *self, PyObject *arg)
{
    (void)self;
    Py_buffer in;
    if (PyObject_GetBuffer(arg, &in, PyBUF_CONTIG_RO) < 0)
        return NULL;
    const uint8_t *base = in.buf;
    cur_t c = {base, base + in.len};

    const uint8_t *type_p = NULL, *chan_p = NULL, *txid_p = NULL;
    uint32_t type_n = 0, chan_n = 0, txid_n = 0;

    /* envelope top dict: must contain payload:B and signature; whole
     * buffer strict canonical with no trailing bytes */
    cur_t payload_v = {NULL, NULL}, sig_v = {NULL, NULL};
    {
        uint32_t n;
        if (dict_enter(&c, &n) < 0) goto reject;
        const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
        while (n--) {
            const uint8_t *k; uint32_t kn;
            if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0) goto reject;
            const uint8_t *vstart = c.p;
            if (canon_value_d(&c, 1) < 0) goto reject;
            if (key_is(k, kn, "payload")) {
                payload_v.p = vstart;
                payload_v.end = c.p;
            } else if (key_is(k, kn, "signature")) {
                sig_v.p = vstart;
                sig_v.end = c.p;
            }
        }
        if (c.p != c.end || !payload_v.p || !sig_v.p) goto reject;
    }

    /* payload must be 'B'; its CONTENT is itself a canonical dict
     * (what Envelope.payload_dict() decodes) */
    {
        const uint8_t *pp; uint32_t pn;
        if (rd_bytes(&payload_v, &pp, &pn) < 0 || payload_v.p != payload_v.end)
            goto reject;
        cur_t pc = {pp, pp + pn};

        cur_t header_v = {NULL, NULL};
        int r = dict_find(&pc, "header", &header_v);
        if (r < 0 || pc.p != pc.end || r == 0) goto reject;

        /* header: needs channel_header AND signature_header (mirror:
         * Header.from_dict KeyErrors without either) */
        cur_t ch_v = {NULL, NULL}, sh_v = {NULL, NULL};
        {
            cur_t hv = header_v;
            if (dict_find(&hv, "channel_header", &ch_v) != 1) goto reject;
            hv = header_v;
            if (dict_find(&hv, "signature_header", &sh_v) != 1) goto reject;
        }
        /* signature_header: creator + nonce keys must exist */
        {
            cur_t t = sh_v, dummy = {NULL, NULL};
            if (dict_find(&t, "creator", &dummy) != 1) goto reject;
            t = sh_v;
            if (dict_find(&t, "nonce", &dummy) != 1) goto reject;
        }
        /* channel_header: type/channel_id/txid strs */
        {
            cur_t t = ch_v, v = {NULL, NULL};
            if (dict_find(&t, "type", &v) != 1
                || rd_str(&v, &type_p, &type_n) < 0 || v.p != v.end)
                goto reject;
            t = ch_v;
            if (dict_find(&t, "channel_id", &v) != 1
                || rd_str(&v, &chan_p, &chan_n) < 0 || v.p != v.end)
                goto reject;
            t = ch_v;
            if (dict_find(&t, "txid", &v) != 1
                || rd_str(&v, &txid_p, &txid_n) < 0 || v.p != v.end)
                goto reject;
        }
    }

    {
        PyObject *res = Py_BuildValue(
            "(s#s#s#)",
            (const char *)type_p, (Py_ssize_t)type_n,
            (const char *)chan_p, (Py_ssize_t)chan_n,
            (const char *)txid_p, (Py_ssize_t)txid_n);
        PyBuffer_Release(&in);
        if (res)
            st_env_accept++;
        return res;
    }

reject:
    PyBuffer_Release(&in);
    st_env_reject++;
    Py_RETURN_NONE;
}

/* ------------------------------------------------------------------ */
/* rwset_lanes — the rw-set lane extractor.
 *
 * rwset_lanes(base_buf, spans_buf) walks every envelope span of a
 * block (spans_buf = n × (u64 off, u64 len) pairs, the same layout
 * parse_block emits) and classifies each tx against the EXACT
 * semantics of ledger/mvcc.parse_endorser_tx + protocol/types
 * from_dict laxity, emitting fixed-width uint64 lanes for the lane
 * table's readers (protocol/wire.py LaneTable: the ledger's MVCC walk,
 * the block store's txid index, the commit notifier, the private-data
 * coordinator):
 *
 *   status 0 OK       strict endorser tx; lanes emitted
 *   status 1 SKIP     parse_endorser_tx provably returns None
 *                     (non-endorser channel-header type, or an empty
 *                     actions list)
 *   status 2 BAD      parse_endorser_tx provably RAISES (the oracle
 *                     stamps BAD_RWSET on a gate-valid tx)
 *   status 3 RANGE    well-formed endorser tx carrying a non-empty
 *                     range_queries list (interval replay is host work)
 *   status 4 UNKNOWN  host outcome is deterministic but device-
 *                     inexpressible (non-str keys, bignum/odd version
 *                     shapes, non-bool is_delete, non-bytes payload…),
 *                     or a header Envelope.header() would refuse
 *
 * RANGE/UNKNOWN txs that could pass the signature gate force the host
 * path for the block (demotion); BAD/SKIP never do.  rw-set keys are
 * interned by a 64-bit djb2 hash over ns||0x00||key through an
 * open-addressed table with byte-exact comparison: two DISTINCT keys
 * sharing a hash set the collision flag and the whole call returns
 * flags=1 so the caller demotes — correctness never depends on hash
 * uniqueness.
 *
 * Return: (flags, n_tx, n_keys, n_reads, n_writes, arena) where the
 * arena holds native-endian u64 cells in four sections:
 *   tx      n_tx    × 3  [status, txid_off, txid_len]
 *   reads   n_reads × 5  [tx, slot, has_version, block_num, tx_num]
 *   writes  n_writes× 5  [tx, slot, is_delete, value_off, value_len]
 *   keys    n_keys  × 5  [hash, ns_off, ns_len, key_off, key_len]
 * All offsets index base_buf.  On collision: (1, 0, 0, 0, 0, None).
 * None for inputs that are not a valid span table over base_buf.
 * Scratch buffers are module-global PyMem_Raw allocations reused
 * across calls — the parse stage stays O(1) Python allocations.  The
 * walk touches no Python object, so it runs WITHOUT the interpreter
 * lock (the validator opens a block's lanes while a resolver thread
 * waits for the device, and that waiter's wake-up time is what the
 * dispatch account books as the program's end); `g_lane_lock` keeps the
 * scratch one caller's from its reset until it is copied out.        */

enum {
    LN_OK = 0, LN_SKIP = 1, LN_BAD = 2, LN_RANGE = 3, LN_UNKNOWN = 4,
    LN_COLL = -1, LN_OOM = -2,
};

static uint64_t *g_tx = NULL;   static size_t g_tx_cap = 0;
static uint64_t *g_rd = NULL;   static size_t g_rd_cap = 0, g_rd_n = 0;
static uint64_t *g_wr = NULL;   static size_t g_wr_cap = 0, g_wr_n = 0;
static uint64_t *g_keys = NULL; static size_t g_keys_cap = 0, g_keys_n = 0;
static uint32_t *g_tab = NULL;  static size_t g_tab_cap = 0;
static PyThread_type_lock g_lane_lock = NULL;

static uint64_t st_rw_accept = 0;     /* lane calls that produced lanes */
static uint64_t st_rw_reject = 0;     /* invalid span-table inputs      */
static uint64_t st_rw_collision = 0;  /* calls demoted on hash collision */
static uint64_t st_rw_keys = 0;       /* unique rw keys interned (cum.) */
static uint64_t st_rw_lanes = 0;      /* read+write lanes emitted (cum.) */

/* -1 when out of memory; no exception is set (no interpreter lock
 * here): py_rwset_lanes raises it */
static int grow_u64(uint64_t **buf, size_t *cap, size_t need)
{
    if (*cap >= need) return 0;
    size_t ncap = *cap ? *cap : 256;
    while (ncap < need) ncap <<= 1;
    uint64_t *nb = PyMem_RawRealloc(*buf, ncap * sizeof(uint64_t));
    if (!nb) return -1;
    *buf = nb;
    *cap = ncap;
    return 0;
}

static int tab_grow(void)
{
    size_t ncap = g_tab_cap ? g_tab_cap * 2 : 64;
    uint32_t *nt = PyMem_RawMalloc(ncap * sizeof(uint32_t));
    if (!nt) return -1;
    memset(nt, 0, ncap * sizeof(uint32_t));
    for (size_t j = 0; j < g_keys_n; j++) {
        size_t i = (size_t)g_keys[5 * j] & (ncap - 1);
        while (nt[i]) i = (i + 1) & (ncap - 1);
        nt[i] = (uint32_t)(j + 1);
    }
    PyMem_RawFree(g_tab);
    g_tab = nt;
    g_tab_cap = ncap;
    return 0;
}

/* slot index, or LN_COLL (same hash, different key bytes) / LN_OOM */
static int64_t intern_key(const uint8_t *base,
                          uint64_t ns_off, uint64_t ns_len,
                          uint64_t key_off, uint64_t key_len)
{
    uint64_t h = 5381, i_;
    const uint8_t *p = base + ns_off;
    for (i_ = 0; i_ < ns_len; i_++) h = h * 33 + p[i_];
    h = h * 33;                        /* the 0x00 ns/key separator */
    p = base + key_off;
    for (i_ = 0; i_ < key_len; i_++) h = h * 33 + p[i_];

    if ((g_keys_n + 1) * 2 > g_tab_cap && tab_grow() < 0)
        return LN_OOM;
    size_t mask = g_tab_cap - 1;
    size_t i = (size_t)h & mask;
    while (g_tab[i]) {
        uint64_t *rec = &g_keys[5 * (size_t)(g_tab[i] - 1)];
        if (rec[0] == h) {
            if (rec[2] == ns_len && rec[4] == key_len
                && memcmp(base + rec[1], base + ns_off, (size_t)ns_len) == 0
                && memcmp(base + rec[3], base + key_off, (size_t)key_len) == 0)
                return (int64_t)(g_tab[i] - 1);
            return LN_COLL;
        }
        i = (i + 1) & mask;
    }
    if (grow_u64(&g_keys, &g_keys_cap, (g_keys_n + 1) * 5) < 0)
        return LN_OOM;
    uint64_t *rec = &g_keys[5 * g_keys_n];
    rec[0] = h;
    rec[1] = ns_off; rec[2] = ns_len;
    rec[3] = key_off; rec[4] = key_len;
    g_tab[i] = (uint32_t)(g_keys_n + 1);
    st_rw_keys++;
    return (int64_t)g_keys_n++;
}

/* Version.from_list mirror: None -> absent; list len<2 raises
 * (IndexError -> BAD); both ints must be fixed 'I' within i32, else
 * the host compare is device-inexpressible (UNKNOWN); extra elements
 * are ignored by from_list.  On any non-OK status the caller abandons
 * the whole envelope, so the cursor may be left mid-value. */
static int walk_version(cur_t *c, uint64_t *has, uint64_t *blk,
                        uint64_t *txn)
{
    if (c->p >= c->end) return LN_BAD;
    uint8_t tag = *c->p;
    if (tag == 'N') { c->p++; return LN_OK; }
    if (tag != 'L') return LN_UNKNOWN;
    c->p++;
    uint32_t n;
    if (rd_u32(c, &n) < 0) return LN_BAD;
    if (n < 2) return LN_BAD;          /* v[0]/v[1] IndexError */
    int64_t v0, v1;
    if (rd_i64(c, &v0) < 0 || v0 < INT32_MIN || v0 > INT32_MAX)
        return LN_UNKNOWN;
    if (rd_i64(c, &v1) < 0 || v1 < INT32_MIN || v1 > INT32_MAX)
        return LN_UNKNOWN;
    for (uint32_t i = 2; i < n; i++)
        if (canon_value_d(c, 1) < 0) return LN_BAD;
    *has = 1;
    *blk = (uint64_t)v0;
    *txn = (uint64_t)v1;
    return LN_OK;
}

static int walk_read(cur_t *c, const uint8_t *base, int emit, uint64_t tx,
                     uint64_t ns_off, uint64_t ns_len)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;  /* d["key"] raises */
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    uint64_t key_off = 0, key_len = 0, has = 0, blk = 0, txn = 0;
    int have_key = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "key")) {
            const uint8_t *sp; uint32_t sn;
            if (c->p >= c->end || *c->p != 'S') return LN_UNKNOWN;
            if (rd_str(c, &sp, &sn) < 0) return LN_BAD;
            key_off = (uint64_t)(sp - base);
            key_len = sn;
            have_key = 1;
        } else if (key_is(k, kn, "version")) {
            int st = walk_version(c, &has, &blk, &txn);
            if (st != LN_OK) return st;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    if (!have_key) return LN_BAD;
    if (!emit) return LN_OK;
    int64_t slot = intern_key(base, ns_off, ns_len, key_off, key_len);
    if (slot < 0) return (int)slot;
    if (grow_u64(&g_rd, &g_rd_cap, (g_rd_n + 1) * 5) < 0) return LN_OOM;
    uint64_t *r = &g_rd[5 * g_rd_n++];
    r[0] = tx; r[1] = (uint64_t)slot; r[2] = has; r[3] = blk; r[4] = txn;
    return LN_OK;
}

static int walk_write(cur_t *c, const uint8_t *base, int emit, uint64_t tx,
                      uint64_t ns_off, uint64_t ns_len)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    uint64_t key_off = 0, key_len = 0, del = 0, voff = 0, vlen = 0;
    int have_key = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "key")) {
            const uint8_t *sp; uint32_t sn;
            if (c->p >= c->end || *c->p != 'S') return LN_UNKNOWN;
            if (rd_str(c, &sp, &sn) < 0) return LN_BAD;
            key_off = (uint64_t)(sp - base);
            key_len = sn;
            have_key = 1;
        } else if (key_is(k, kn, "is_delete")) {
            if (c->p >= c->end) return LN_BAD;
            if (*c->p == 'T') del = 1;
            else if (*c->p == 'F') del = 0;
            else return LN_UNKNOWN;    /* truthy non-bool: mirrorable
                                        * host-side only */
            c->p++;
        } else if (key_is(k, kn, "value")) {
            const uint8_t *bp; uint32_t bn;
            if (c->p >= c->end || *c->p != 'B') return LN_UNKNOWN;
            if (rd_bytes(c, &bp, &bn) < 0) return LN_BAD;
            voff = (uint64_t)(bp - base);
            vlen = bn;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    if (!have_key) return LN_BAD;
    if (!emit) return LN_OK;
    int64_t slot = intern_key(base, ns_off, ns_len, key_off, key_len);
    if (slot < 0) return (int)slot;
    if (grow_u64(&g_wr, &g_wr_cap, (g_wr_n + 1) * 5) < 0) return LN_OOM;
    uint64_t *w = &g_wr[5 * g_wr_n++];
    w[0] = tx; w[1] = (uint64_t)slot; w[2] = del; w[3] = voff; w[4] = vlen;
    return LN_OK;
}

/* One NsRwSet dict.  Canonical key order namespace < range_queries <
 * reads < writes guarantees the namespace span is known before any
 * lane is emitted; a reads/writes key reached without it means
 * d["namespace"] raises (sorted keys cannot produce it later). */
static int walk_ns(cur_t *c, const uint8_t *base, int emit, uint64_t tx)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    uint64_t ns_off = 0, ns_len = 0;
    int have_ns = 0, have_reads = 0, have_writes = 0, saw_range = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "namespace")) {
            const uint8_t *sp; uint32_t sn;
            if (c->p >= c->end || *c->p != 'S') return LN_UNKNOWN;
            if (rd_str(c, &sp, &sn) < 0) return LN_BAD;
            ns_off = (uint64_t)(sp - base);
            ns_len = sn;
            have_ns = 1;
        } else if (key_is(k, kn, "reads")) {
            if (!have_ns) return LN_BAD;
            if (c->p >= c->end || *c->p != 'L') return LN_UNKNOWN;
            c->p++;
            uint32_t rn;
            if (rd_u32(c, &rn) < 0) return LN_BAD;
            while (rn--) {
                int st = walk_read(c, base, emit, tx, ns_off, ns_len);
                if (st != LN_OK) return st;
            }
            have_reads = 1;
        } else if (key_is(k, kn, "writes")) {
            if (!have_ns) return LN_BAD;
            if (c->p >= c->end || *c->p != 'L') return LN_UNKNOWN;
            c->p++;
            uint32_t wn;
            if (rd_u32(c, &wn) < 0) return LN_BAD;
            while (wn--) {
                int st = walk_write(c, base, emit, tx, ns_off, ns_len);
                if (st != LN_OK) return st;
            }
            have_writes = 1;
        } else if (key_is(k, kn, "range_queries")) {
            if (c->p >= c->end || *c->p != 'L') return LN_UNKNOWN;
            cur_t peek = *c;
            peek.p++;
            uint32_t qn;
            if (rd_u32(&peek, &qn) < 0) return LN_BAD;
            if (canon_value_d(c, 1) < 0) return LN_BAD;
            if (qn > 0) saw_range = 1;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    if (!have_ns || !have_reads || !have_writes) return LN_BAD;
    return saw_range ? LN_RANGE : LN_OK;
}

static int walk_rwset(cur_t *c, const uint8_t *base, int emit, uint64_t tx)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;  /* d["ns"] raises */
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    int have_ns_list = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "ns")) {
            if (c->p >= c->end || *c->p != 'L') return LN_UNKNOWN;
            c->p++;
            uint32_t ln;
            if (rd_u32(c, &ln) < 0) return LN_BAD;
            while (ln--) {
                int st = walk_ns(c, base, emit, tx);
                if (st != LN_OK) return st;
            }
            have_ns_list = 1;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    return have_ns_list ? LN_OK : LN_BAD;
}

static int walk_endorsement(cur_t *c)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    int have_e = 0, have_s = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "endorser")) have_e = 1;
        else if (key_is(k, kn, "signature")) have_s = 1;
        if (canon_value_d(c, 1) < 0) return LN_BAD;
    }
    return (have_e && have_s) ? LN_OK : LN_BAD;
}

static int walk_cc_action(cur_t *c, const uint8_t *base, int emit,
                          uint64_t tx)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    int have_id = 0, have_ver = 0, have_rw = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "chaincode_id")) {
            have_id = 1;
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        } else if (key_is(k, kn, "chaincode_version")) {
            have_ver = 1;
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        } else if (key_is(k, kn, "rwset")) {
            int st = walk_rwset(c, base, emit, tx);
            if (st != LN_OK) return st;
            have_rw = 1;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    return (have_id && have_ver && have_rw) ? LN_OK : LN_BAD;
}

static int walk_action(cur_t *c, const uint8_t *base, int emit, uint64_t tx)
{
    uint32_t n;
    if (dict_enter(c, &n) < 0) return LN_BAD;
    const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
    int have_ph = 0, have_act = 0, have_end = 0;
    while (n--) {
        const uint8_t *k; uint32_t kn;
        if (dict_key(c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
        if (key_is(k, kn, "action")) {
            int st = walk_cc_action(c, base, emit, tx);
            if (st != LN_OK) return st;
            have_act = 1;
        } else if (key_is(k, kn, "endorsements")) {
            if (c->p >= c->end || *c->p != 'L') return LN_UNKNOWN;
            c->p++;
            uint32_t en;
            if (rd_u32(c, &en) < 0) return LN_BAD;
            while (en--) {
                int st = walk_endorsement(c);
                if (st != LN_OK) return st;
            }
            have_end = 1;
        } else if (key_is(k, kn, "proposal_hash")) {
            have_ph = 1;
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        } else {
            if (canon_value_d(c, 1) < 0) return LN_BAD;
        }
    }
    return (have_ph && have_act && have_end) ? LN_OK : LN_BAD;
}

/* Classify one envelope span; emit lanes for the first action's rwset
 * of an OK endorser tx.  Every decision mirrors a step of
 * Envelope.deserialize -> parse_endorser_tx (see module comment for
 * the status contract); evaluation ORDER matters only where it
 * changes the outcome class — notably ch["txid"] is only read after
 * Transaction.from_dict and the empty-actions check. */
static int walk_env(const uint8_t *base, const uint8_t *ep, size_t en,
                    uint64_t tx, uint64_t *txid_off, uint64_t *txid_len)
{
    cur_t c = {ep, ep + en};
    cur_t payload_v = {NULL, NULL};
    int have_sig = 0;
    uint32_t n;
    if (dict_enter(&c, &n) < 0) return LN_BAD;
    {
        const uint8_t *kprev = NULL; uint32_t kprev_n = 0;
        while (n--) {
            const uint8_t *k; uint32_t kn;
            if (dict_key(&c, &kprev, &kprev_n, &k, &kn) < 0) return LN_BAD;
            const uint8_t *vstart = c.p;
            if (canon_value_d(&c, 1) < 0) return LN_BAD;
            if (key_is(k, kn, "payload")) {
                payload_v.p = vstart;
                payload_v.end = c.p;
            } else if (key_is(k, kn, "signature")) {
                have_sig = 1;
            }
        }
    }
    if (c.p != c.end) return LN_BAD;
    if (!payload_v.p || !have_sig) return LN_BAD;   /* KeyError */
    if (*payload_v.p != 'B') return LN_UNKNOWN;     /* decode(non-bytes) */

    const uint8_t *pp; uint32_t pn;
    if (rd_bytes(&payload_v, &pp, &pn) < 0) return LN_BAD;

    cur_t header_v = {NULL, NULL};
    {
        cur_t pc = {pp, pp + pn};
        int r = dict_find(&pc, "header", &header_v);
        if (r != 1 || pc.p != pc.end) return LN_BAD;
    }
    cur_t ch_v = {NULL, NULL};
    {
        cur_t t = header_v;
        if (dict_find(&t, "channel_header", &ch_v) != 1) return LN_BAD;
    }
    {
        cur_t t = ch_v, type_v = {NULL, NULL};
        if (dict_find(&t, "type", &type_v) != 1) return LN_BAD;
        const uint8_t *sp; uint32_t sn;
        if (type_v.p >= type_v.end || *type_v.p != 'S')
            return LN_SKIP;            /* non-str != TX_ENDORSER */
        if (rd_str(&type_v, &sp, &sn) < 0) return LN_BAD;
        if (!key_is(sp, sn, "endorser_transaction")) return LN_SKIP;
    }
    cur_t data_v = {NULL, NULL};
    {
        cur_t pc = {pp, pp + pn};
        if (dict_find(&pc, "data", &data_v) != 1) return LN_BAD;
    }
    cur_t actions_v = {NULL, NULL};
    {
        cur_t t = data_v;
        if (dict_find(&t, "actions", &actions_v) != 1) return LN_BAD;
    }
    if (actions_v.p >= actions_v.end || *actions_v.p != 'L')
        return LN_UNKNOWN;
    {
        cur_t t = actions_v;
        t.p++;
        uint32_t an;
        if (rd_u32(&t, &an) < 0) return LN_BAD;
        if (an == 0) return LN_SKIP;   /* `not tx.actions` -> None,
                                        * BEFORE ch["txid"] is read */
        for (uint32_t i = 0; i < an; i++) {
            int st = walk_action(&t, base, i == 0, tx);
            if (st != LN_OK) return st;
        }
    }
    {
        cur_t t = ch_v, txid_v = {NULL, NULL};
        if (dict_find(&t, "txid", &txid_v) != 1) return LN_BAD;
        const uint8_t *sp; uint32_t sn;
        if (txid_v.p >= txid_v.end || *txid_v.p != 'S') return LN_UNKNOWN;
        if (rd_str(&txid_v, &sp, &sn) < 0) return LN_BAD;
        *txid_off = (uint64_t)(sp - base);
        *txid_len = sn;
    }
    /* OK also tells the txid readers (block index, commit notifier,
     * private-data coordinator) that Envelope.header() succeeds: it
     * needs channel_id, and creator + nonce in a signature_header.
     * parse_endorser_tx reads none of them, so a tx without them is not
     * BAD; the table just cannot speak for it */
    {
        cur_t t = ch_v, v = {NULL, NULL}, sh_v = {NULL, NULL};
        if (dict_find(&t, "channel_id", &v) != 1) return LN_UNKNOWN;
        t = header_v;
        if (dict_find(&t, "signature_header", &sh_v) != 1)
            return LN_UNKNOWN;
        t = sh_v;
        if (dict_find(&t, "creator", &v) != 1) return LN_UNKNOWN;
        t = sh_v;
        if (dict_find(&t, "nonce", &v) != 1) return LN_UNKNOWN;
    }
    return LN_OK;
}

static PyObject *py_rwset_lanes(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer in, sp;
    if (!PyArg_ParseTuple(args, "y*y*", &in, &sp))
        return NULL;
    if (sp.len % 16) {
        PyBuffer_Release(&in);
        PyBuffer_Release(&sp);
        st_rw_reject++;
        Py_RETURN_NONE;
    }
    const uint8_t *base = in.buf;
    size_t blen = (size_t)in.len;
    size_t T = (size_t)sp.len / 16;

    /* what ended the walk early: a span outside base, two keys under
     * one hash, or no memory */
    enum { W_DONE, W_REJECT, W_COLLISION, W_OOM } end = W_DONE;
    Py_BEGIN_ALLOW_THREADS
    PyThread_acquire_lock(g_lane_lock, WAIT_LOCK);
    g_rd_n = g_wr_n = g_keys_n = 0;
    if (g_tab)
        memset(g_tab, 0, g_tab_cap * sizeof(uint32_t));
    if (grow_u64(&g_tx, &g_tx_cap, T ? T * 3 : 1) < 0)
        end = W_OOM;
    for (size_t t = 0; t < T && end == W_DONE; t++) {
        uint64_t sv[2];
        memcpy(sv, (const uint8_t *)sp.buf + 16 * t, 16);
        if (sv[0] > blen || sv[1] > blen - sv[0]) {
            end = W_REJECT;
            break;
        }
        size_t rd_mark = g_rd_n, wr_mark = g_wr_n;
        uint64_t txo = 0, txl = 0;
        int st = walk_env(base, base + sv[0], (size_t)sv[1],
                          (uint64_t)t, &txo, &txl);
        if (st == LN_OOM) {
            end = W_OOM;
            break;
        }
        if (st == LN_COLL) {
            end = W_COLLISION;
            break;
        }
        if (st != LN_OK) {             /* drop this tx's partial lanes */
            g_rd_n = rd_mark;
            g_wr_n = wr_mark;
            txo = txl = 0;
        }
        g_tx[3 * t] = (uint64_t)st;
        g_tx[3 * t + 1] = txo;
        g_tx[3 * t + 2] = txl;
    }
    Py_END_ALLOW_THREADS
    /* g_lane_lock is still held: the scratch is copied out below */
    PyObject *res = NULL;
    if (end == W_REJECT) {
        st_rw_reject++;
        res = Py_NewRef(Py_None);
    } else if (end == W_COLLISION) {
        st_rw_collision++;
        res = Py_BuildValue("(iKKKKO)", 1, 0ULL, 0ULL, 0ULL, 0ULL,
                            Py_None);
    } else if (end == W_OOM) {
        PyErr_NoMemory();
    } else {
        size_t R = g_rd_n, W = g_wr_n, K = g_keys_n;
        size_t cells = T * 3 + (R + W + K) * 5;
        FPArena *a = arena_acquire(cells ? cells * 8 : 8);
        if (a) {
            uint64_t *o = (uint64_t *)a->buf;
            if (T) { memcpy(o, g_tx, T * 3 * 8); o += T * 3; }
            if (R) { memcpy(o, g_rd, R * 5 * 8); o += R * 5; }
            if (W) { memcpy(o, g_wr, W * 5 * 8); o += W * 5; }
            if (K) { memcpy(o, g_keys, K * 5 * 8); }
            a->len = (Py_ssize_t)(cells * 8);
            st_rw_accept++;
            st_rw_lanes += R + W;
            res = Py_BuildValue(
                "(iKKKKN)", 0,
                (unsigned long long)T, (unsigned long long)K,
                (unsigned long long)R, (unsigned long long)W,
                (PyObject *)a);
        }
    }
    PyThread_release_lock(g_lane_lock);
    PyBuffer_Release(&in);
    PyBuffer_Release(&sp);
    return res;
}

/* ------------------------------------------------------------------ */
/* the arena's strings, for the host                                   */

/* arena_keys(base, keys) -> [(ns, key)] of the arena's keys section
 * (n_keys x 5 cells [hash, ns_off, ns_len, key_off, key_len]), decoded
 * once a slot: what wire.LaneTable.key_strs gives.  A namespace equal to
 * the slot before's is the same str object (a block names a handful). */
static PyObject *py_arena_keys(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer in, kb;
    if (!PyArg_ParseTuple(args, "y*y*", &in, &kb))
        return NULL;
    PyObject *out = NULL, *prev_ns = NULL;
    if (kb.len % 40) {
        PyErr_SetString(PyExc_ValueError, "arena_keys: keys is n x 5 cells");
        goto done;
    }
    const char *base = in.buf;
    uint64_t blen = (uint64_t)in.len, p_off = 0, p_len = 0;
    Py_ssize_t n = kb.len / 40;
    out = PyList_New(n);
    if (!out)
        goto done;
    for (Py_ssize_t i = 0; i < n; i++) {
        uint64_t c[5];
        memcpy(c, (const uint8_t *)kb.buf + 40 * i, 40);
        if (c[1] > blen || c[2] > blen - c[1]
                || c[3] > blen || c[4] > blen - c[3]) {
            PyErr_SetString(PyExc_ValueError,
                            "arena_keys: key span outside base");
            Py_CLEAR(out);
            goto done;
        }
        if (!prev_ns || c[2] != p_len
                || memcmp(base + c[1], base + p_off, (size_t)p_len)) {
            Py_XDECREF(prev_ns);
            prev_ns = PyUnicode_DecodeUTF8(base + c[1], (Py_ssize_t)c[2],
                                           "strict");
            p_off = c[1];
            p_len = c[2];
        }
        PyObject *key = prev_ns ? PyUnicode_DecodeUTF8(
            base + c[3], (Py_ssize_t)c[4], "strict") : NULL;
        PyObject *pair = key ? PyTuple_Pack(2, prev_ns, key) : NULL;
        Py_XDECREF(key);
        if (!pair) {
            Py_CLEAR(out);
            goto done;
        }
        PyList_SET_ITEM(out, i, pair);
    }
done:
    Py_XDECREF(prev_ns);
    PyBuffer_Release(&in);
    PyBuffer_Release(&kb);
    return out;
}

/* arena_txids(base, tx) -> [txid | None] of the arena's tx section
 * (n_tx x 3 cells [status, txid_off, txid_len]): None where the status
 * is not OK.  What wire.LaneTable.txids gives. */
static PyObject *py_arena_txids(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer in, tb;
    if (!PyArg_ParseTuple(args, "y*y*", &in, &tb))
        return NULL;
    PyObject *out = NULL;
    if (tb.len % 24) {
        PyErr_SetString(PyExc_ValueError, "arena_txids: tx is n x 3 cells");
        goto done;
    }
    uint64_t blen = (uint64_t)in.len;
    Py_ssize_t n = tb.len / 24;
    out = PyList_New(n);
    if (!out)
        goto done;
    for (Py_ssize_t i = 0; i < n; i++) {
        uint64_t c[3];
        memcpy(c, (const uint8_t *)tb.buf + 24 * i, 24);
        PyObject *txid;
        if (c[0] != LN_OK) {
            txid = Py_None;
            Py_INCREF(txid);
        } else if (c[1] > blen || c[2] > blen - c[1]) {
            PyErr_SetString(PyExc_ValueError,
                            "arena_txids: txid span outside base");
            txid = NULL;
        } else {
            txid = PyUnicode_DecodeUTF8((const char *)in.buf + c[1],
                                        (Py_ssize_t)c[2], "strict");
        }
        if (!txid) {
            Py_CLEAR(out);
            goto done;
        }
        PyList_SET_ITEM(out, i, txid);
    }
done:
    PyBuffer_Release(&in);
    PyBuffer_Release(&tb);
    return out;
}

/* ------------------------------------------------------------------ */
/* stats                                                               */

static PyObject *py_stats(PyObject *self, PyObject *noarg)
{
    (void)self;
    (void)noarg;
    return Py_BuildValue(
        "{s:K,s:K,s:K,s:i,s:K,s:K,s:K,s:K,"
        "s:K,s:K,s:K,s:K,s:K,s:K}",
        "pool_hit", (unsigned long long)st_pool_hit,
        "pool_miss", (unsigned long long)st_pool_miss,
        "pool_drop", (unsigned long long)st_pool_drop,
        "pool_free", pool_n,
        "block_accept", (unsigned long long)st_blk_accept,
        "block_reject", (unsigned long long)st_blk_reject,
        "env_accept", (unsigned long long)st_env_accept,
        "env_reject", (unsigned long long)st_env_reject,
        "rw_accept", (unsigned long long)st_rw_accept,
        "rw_reject", (unsigned long long)st_rw_reject,
        "rw_collision", (unsigned long long)st_rw_collision,
        "rw_keys", (unsigned long long)st_rw_keys,
        "rw_lanes", (unsigned long long)st_rw_lanes,
        "rw_table_slots", (unsigned long long)g_tab_cap);
}

/* ------------------------------------------------------------------ */

static PyMethodDef methods[] = {
    {"parse_block", py_parse_block, METH_O,
     "parse_block(buf) -> (number, prev_hash, data_hash, data_off, "
     "data_end, n, spans, meta_val_off) | None"},
    {"envelope_summary", py_envelope_summary, METH_O,
     "envelope_summary(buf) -> (type, channel_id, txid) | None"},
    {"rwset_lanes", py_rwset_lanes, METH_VARARGS,
     "rwset_lanes(base, spans) -> (flags, n_tx, n_keys, n_reads, "
     "n_writes, arena) | None"},
    {"arena_keys", py_arena_keys, METH_VARARGS,
     "arena_keys(base, keys) -> [(ns, key)] of the arena's keys section"},
    {"arena_txids", py_arena_txids, METH_VARARGS,
     "arena_txids(base, tx) -> [txid | None] of the arena's tx section"},
    {"stats", py_stats, METH_NOARGS,
     "stats() -> arena-pool, accept/reject and rw-lane counters"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_fastparse",
    "zero-copy wire-to-device block/envelope span parser", -1, methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC PyInit__fastparse(void)
{
    if (PyType_Ready(&FPArenaType) < 0)
        return NULL;
    if (!g_lane_lock && !(g_lane_lock = PyThread_allocate_lock()))
        return PyErr_NoMemory();
    PyObject *m = PyModule_Create(&moduledef);
    if (!m)
        return NULL;
    Py_INCREF(&FPArenaType);
    if (PyModule_AddObject(m, "Arena", (PyObject *)&FPArenaType) < 0) {
        Py_DECREF(&FPArenaType);
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
