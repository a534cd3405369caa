/* _fastmvcc — the commit path's MVCC walk over a block's lane table, as
 * passes over arrays (ledger/mvcc.py `_array_walk`), and the state
 * store's key hash (ledger/statedb.py `shard_of`).
 *
 *   shard_of(ns, key, n_shards) -> int
 *       FNV-1a 64 over the UTF-8 of ns + "\0" + key, mod n_shards (0 for
 *       n_shards <= 1).  Placement is persistent: checkpoints, the WALs'
 *       replay, snapshots and prepared batches all depend on it, so this
 *       is `statedb._shard_of_py` bit for bit or it is wrong.
 *   slot_shards(base, keys, n_shards) -> bytes (int32 a slot)
 *       the same hash for every interned slot of a lane table, over the
 *       key bytes where they lie in the block (`keys`: the arena's
 *       n_keys x 5 section [hash, ns_off, ns_len, key_off, key_len]; the
 *       extractor admits only valid UTF-8, so the bytes are what
 *       str.encode gives back).
 *   fetch_versions(data, key_strs, shards, shard, has, blk, txn)
 *       for each slot of `shard`: the version `data` (one state shard's
 *       dict, its lock held by the caller) keeps for key_strs[slot],
 *       into the three arrays.
 *   walk(tx, reads, writes, flags, has, blk, txn, block_num, companion,
 *        codes) -> (reads, conflicts_block, conflicts_state, staged)
 *       validateKVRead for every still-VALID tx, in lane order, exactly
 *       as mvcc.validate_and_prepare_batch has it; see py_walk.
 *
 * No function here releases the GIL or calls back into Python code
 * other than dict look-ups of (str, str) keys and attribute reads of
 * the store's frozen dataclasses.
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>
#include <string.h>

#define FNV_OFFSET 0xCBF29CE484222325ULL
#define FNV_PRIME 0x100000001B3ULL

/* lane statuses (protocol/wire.py LANE_*) */
#define LN_SKIP 1
#define LN_BAD 2

static inline uint64_t fnv1a(uint64_t h, const uint8_t *p, size_t n)
{
    for (size_t i = 0; i < n; i++)
        h = (h ^ p[i]) * FNV_PRIME;
    return h;
}

static inline uint64_t key_hash(const uint8_t *ns, size_t ns_len,
                                const uint8_t *key, size_t key_len)
{
    uint64_t h = fnv1a(FNV_OFFSET, ns, ns_len);
    h = (h ^ 0) * FNV_PRIME;                    /* the NUL between them */
    return fnv1a(h, key, key_len);
}

static PyObject *py_shard_of(PyObject *self, PyObject *const *args,
                             Py_ssize_t nargs)
{
    (void)self;
    if (nargs != 3) {
        PyErr_SetString(PyExc_TypeError,
                        "shard_of(ns, key, n_shards) takes 3 arguments");
        return NULL;
    }
    long long n_shards = PyLong_AsLongLong(args[2]);
    if (n_shards == -1 && PyErr_Occurred())
        return NULL;
    if (!PyUnicode_Check(args[0]) || !PyUnicode_Check(args[1])) {
        PyErr_SetString(PyExc_TypeError, "shard_of: ns and key are str");
        return NULL;
    }
    if (n_shards <= 1)
        return PyLong_FromLong(0);
    Py_ssize_t ns_len, key_len;
    const char *ns = PyUnicode_AsUTF8AndSize(args[0], &ns_len);
    if (!ns)
        return NULL;
    const char *key = PyUnicode_AsUTF8AndSize(args[1], &key_len);
    if (!key)
        return NULL;
    uint64_t h = key_hash((const uint8_t *)ns, (size_t)ns_len,
                          (const uint8_t *)key, (size_t)key_len);
    return PyLong_FromUnsignedLongLong(h % (uint64_t)n_shards);
}

static PyObject *py_slot_shards(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer base, keys;
    long long n_shards;
    if (!PyArg_ParseTuple(args, "y*y*L", &base, &keys, &n_shards))
        return NULL;
    PyObject *out = NULL;
    if (keys.len % 40) {
        PyErr_SetString(PyExc_ValueError, "slot_shards: keys is n x 5 u64");
        goto done;
    }
    Py_ssize_t n = keys.len / 40;
    if (n_shards > INT32_MAX) {
        PyErr_SetString(PyExc_ValueError, "slot_shards: n_shards > int32");
        goto done;
    }
    out = PyBytes_FromStringAndSize(NULL, n * 4);
    if (!out)
        goto done;
    int32_t *o = (int32_t *)PyBytes_AS_STRING(out);
    const uint8_t *b = base.buf;
    uint64_t blen = (uint64_t)base.len;
    for (Py_ssize_t i = 0; i < n; i++) {
        uint64_t c[5];
        memcpy(c, (const uint8_t *)keys.buf + 40 * i, 40);
        if (c[1] > blen || c[2] > blen - c[1]
                || c[3] > blen || c[4] > blen - c[3]) {
            PyErr_SetString(PyExc_ValueError,
                            "slot_shards: key span outside base");
            Py_CLEAR(out);
            goto done;
        }
        int32_t shard = 0;
        if (n_shards > 1)
            shard = (int32_t)(key_hash(b + c[1], (size_t)c[2],
                                       b + c[3], (size_t)c[4])
                              % (uint64_t)n_shards);
        memcpy(o + i, &shard, 4);
    }
done:
    PyBuffer_Release(&base);
    PyBuffer_Release(&keys);
    return out;
}

static PyObject *s_version, *s_block_num, *s_tx_num;

static int attr_i64(PyObject *obj, PyObject *name, int64_t *out)
{
    PyObject *v = PyObject_GetAttr(obj, name);
    if (!v)
        return -1;
    long long x = PyLong_AsLongLong(v);
    Py_DECREF(v);
    if (x == -1 && PyErr_Occurred())
        return -1;
    *out = (int64_t)x;
    return 0;
}

static PyObject *py_fetch_versions(PyObject *self, PyObject *args)
{
    (void)self;
    PyObject *data, *key_strs;
    Py_buffer shards, has, blk, txn;
    long long shard;
    if (!PyArg_ParseTuple(args, "O!O!y*Lw*w*w*", &PyDict_Type, &data,
                          &PyList_Type, &key_strs, &shards, &shard,
                          &has, &blk, &txn))
        return NULL;
    PyObject *res = NULL;
    Py_ssize_t n = PyList_GET_SIZE(key_strs);
    if (shards.len != 4 * n || has.len != n || blk.len != 8 * n
            || txn.len != 8 * n) {
        PyErr_SetString(PyExc_ValueError,
                        "fetch_versions: arrays do not match key_strs");
        goto done;
    }
    const int32_t *sh = shards.buf;
    uint8_t *h = has.buf;
    int64_t *b = blk.buf, *t = txn.buf;
    for (Py_ssize_t i = 0; i < n; i++) {
        if (sh[i] != shard)
            continue;
        /* a look-up of a (str, str) tuple runs no Python code, so the
         * list cannot shrink under this loop */
        PyObject *vv = PyDict_GetItemWithError(
            data, PyList_GET_ITEM(key_strs, i));
        if (!vv || vv == Py_None) {
            if (!vv && PyErr_Occurred())
                goto done;
            h[i] = 0;
            b[i] = t[i] = 0;
            continue;
        }
        PyObject *version = PyObject_GetAttr(vv, s_version);
        if (!version)
            goto done;
        int bad = (attr_i64(version, s_block_num, &b[i]) < 0
                   || attr_i64(version, s_tx_num, &t[i]) < 0);
        Py_DECREF(version);
        if (bad)
            goto done;
        h[i] = 1;
    }
    res = Py_None;
    Py_INCREF(res);
done:
    PyBuffer_Release(&shards);
    PyBuffer_Release(&has);
    PyBuffer_Release(&blk);
    PyBuffer_Release(&txn);
    return res;
}

/* walk — the decision, in lane order.
 *
 *   tx         n_tx x 3 i64   [status, txid_off, txid_len]
 *   reads      n_r x 5 i64    [tx, slot, has_version, block, txnum]
 *   writes     n_w x 5 i64    [tx, slot, is_delete, value_off, len]
 *   flags      n_tx u8, in and out: the gate's codes, flipped here
 *   has/blk/txn  n_ids: the committed version of each ident (a slot, or
 *              past the slots a key only `companion` names)
 *   companion  None | n_w i64: for a write lane that deletes (ns, key),
 *              the ident of its validation parameter (`ns#meta`, key),
 *              -1 where there is nothing to drop
 *   codes      (VALID, MVCC_READ_CONFLICT, BAD_RWSET)
 *
 * For each tx whose flag is VALID: status SKIP is passed over, status BAD
 * gives BAD_RWSET, otherwise each read is compared with what an earlier
 * valid tx of this block staged for its slot (a put is (block_num, tx), a
 * delete is nil) and, where nothing was staged, with the committed
 * version; nil equals a read without a version.  `reads` counts every
 * read looked at, the failing one included; the first mismatch flips the
 * flag and ends the tx.  A valid tx's write lanes update the staged table
 * and, after all of them, each key it deletes takes its parameter along
 * where one is staged or committed (mvcc._stage_writes).
 *
 * `staged` is int64 pairs, in order: (write row, -1) for a write lane
 * that survived, (write row, ident) for a parameter dropped with the
 * delete of that row.
 */
static PyObject *py_walk(PyObject *self, PyObject *args)
{
    (void)self;
    Py_buffer txb, rdb, wrb, flb, hasb, blkb, txnb, compb;
    PyObject *comp_obj;
    long long block_num;
    int c_valid, c_conflict, c_bad;
    compb.buf = NULL;
    if (!PyArg_ParseTuple(args, "y*y*y*w*y*y*y*LO(iii)", &txb, &rdb, &wrb,
                          &flb, &hasb, &blkb, &txnb, &block_num, &comp_obj,
                          &c_valid, &c_conflict, &c_bad))
        return NULL;
    PyObject *res = NULL, *out = NULL;
    uint8_t *st_kind = NULL;
    int64_t *st_tx = NULL;
    int have_comp = 0;
    if (comp_obj != Py_None) {
        if (PyObject_GetBuffer(comp_obj, &compb, PyBUF_SIMPLE) < 0)
            goto done;
        have_comp = 1;
    }
    Py_ssize_t n_tx = flb.len, n_r = rdb.len / 40, n_w = wrb.len / 40;
    Py_ssize_t n_ids = hasb.len;
    if (txb.len != 24 * n_tx || rdb.len % 40 || wrb.len % 40
            || blkb.len != 8 * n_ids || txnb.len != 8 * n_ids
            || (have_comp && compb.len != 8 * n_w)) {
        PyErr_SetString(PyExc_ValueError, "walk: array shapes disagree");
        goto done;
    }
    const int64_t *txc = txb.buf, *rd = rdb.buf, *wr = wrb.buf;
    const int64_t *blk = blkb.buf, *txn = txnb.buf;
    const int64_t *comp = have_comp ? compb.buf : NULL;
    const uint8_t *has = hasb.buf;
    uint8_t *flags = flb.buf;
    /* what valid txs of this block staged so far, by ident */
    enum { NONE = 0, PUT = 1, DEL = 2 };
    st_kind = calloc(n_ids ? (size_t)n_ids : 1, 1);
    st_tx = malloc((n_ids ? (size_t)n_ids : 1) * sizeof(int64_t));
    out = PyBytes_FromStringAndSize(NULL, (have_comp ? 2 : 1) * n_w * 16);
    if (!st_kind || !st_tx || !out) {
        if (out)
            PyErr_NoMemory();
        goto done;
    }
    int64_t *o = (int64_t *)PyBytes_AS_STRING(out);
    Py_ssize_t n_out = 0;
    long long reads = 0, against_block = 0, against_state = 0;
    Py_ssize_t r = 0, w = 0;
    for (Py_ssize_t tx = 0; tx < n_tx; tx++) {
        Py_ssize_t r0 = r, w0 = w;
        while (r < n_r && rd[5 * r] == tx)
            r++;
        while (w < n_w && wr[5 * w] == tx)
            w++;
        if ((r < n_r && rd[5 * r] < tx) || (w < n_w && wr[5 * w] < tx))
            goto unsorted;
        int64_t status = txc[3 * tx];
        if (status == LN_SKIP || flags[tx] != c_valid)
            continue;
        if (status == LN_BAD) {
            flags[tx] = (uint8_t)c_bad;
            continue;
        }
        int ok = 1;
        for (Py_ssize_t i = r0; i < r; i++) {
            const int64_t *row = rd + 5 * i;
            int64_t slot = row[1];
            if (slot < 0 || slot >= n_ids)
                goto range;
            int has_v = row[2] != 0, match;
            reads++;
            if (st_kind[slot] != NONE) {
                match = st_kind[slot] == PUT
                    ? (has_v && row[3] == block_num
                       && row[4] == st_tx[slot])
                    : !has_v;
                against_block += !match;
            } else {
                match = has[slot]
                    ? (has_v && row[3] == blk[slot] && row[4] == txn[slot])
                    : !has_v;
                against_state += !match;
            }
            if (!match) {
                flags[tx] = (uint8_t)c_conflict;
                ok = 0;
                break;
            }
        }
        if (!ok)
            continue;
        for (Py_ssize_t i = w0; i < w; i++) {
            int64_t slot = wr[5 * i + 1];
            if (slot < 0 || slot >= n_ids)
                goto range;
            st_kind[slot] = wr[5 * i + 2] ? DEL : PUT;
            st_tx[slot] = tx;
            o[2 * n_out] = i;
            o[2 * n_out + 1] = -1;
            n_out++;
        }
        if (!comp)
            continue;
        /* after all of the tx's own writes: the delete wins over a
         * parameter the same rw-set sets */
        for (Py_ssize_t i = w0; i < w; i++) {
            int64_t c = comp[i];
            if (!wr[5 * i + 2] || c < 0)
                continue;
            if (c >= n_ids)
                goto range;
            if (st_kind[c] == PUT || (st_kind[c] == NONE && has[c])) {
                st_kind[c] = DEL;
                st_tx[c] = tx;
                o[2 * n_out] = i;
                o[2 * n_out + 1] = c;
                n_out++;
            }
        }
    }
    if (r != n_r || w != n_w)
        goto unsorted;
    if (_PyBytes_Resize(&out, n_out * 16) < 0)
        goto done;
    res = Py_BuildValue("(LLLO)", reads, against_block, against_state, out);
    goto done;
unsorted:
    PyErr_SetString(PyExc_ValueError,
                    "walk: lanes do not ascend by tx within the block");
    goto done;
range:
    PyErr_SetString(PyExc_ValueError, "walk: ident outside the arrays");
done:
    Py_XDECREF(out);
    free(st_kind);
    free(st_tx);
    if (have_comp)
        PyBuffer_Release(&compb);
    PyBuffer_Release(&txb);
    PyBuffer_Release(&rdb);
    PyBuffer_Release(&wrb);
    PyBuffer_Release(&flb);
    PyBuffer_Release(&hasb);
    PyBuffer_Release(&blkb);
    PyBuffer_Release(&txnb);
    return res;
}

static PyMethodDef methods[] = {
    {"shard_of", (PyCFunction)(void (*)(void))py_shard_of, METH_FASTCALL,
     "shard_of(ns, key, n_shards) -> FNV-1a 64 of ns NUL key, mod n_shards"},
    {"slot_shards", py_slot_shards, METH_VARARGS,
     "slot_shards(base, keys, n_shards) -> int32 shard of each slot"},
    {"fetch_versions", py_fetch_versions, METH_VARARGS,
     "fetch_versions(data, key_strs, shards, shard, has, blk, txn)"},
    {"walk", py_walk, METH_VARARGS,
     "walk(tx, reads, writes, flags, has, blk, txn, block_num, companion, "
     "codes) -> (reads, conflicts_block, conflicts_state, staged)"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_fastmvcc",
    "MVCC over a lane table as array passes; the state store's key hash",
    -1, methods, NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC PyInit__fastmvcc(void)
{
    s_version = PyUnicode_InternFromString("version");
    s_block_num = PyUnicode_InternFromString("block_num");
    s_tx_num = PyUnicode_InternFromString("tx_num");
    if (!s_version || !s_block_num || !s_tx_num)
        return NULL;
    return PyModule_Create(&moduledef);
}
