/* kwok_fastdrain — CPython extension for the device drain's per-row
 * hot loops (VERDICT r02 next-#1: C-backed substitution + columnar
 * store commit so per-op dicts/copies disappear).
 *
 * Everything here is a drop-in accelerator for a pure-Python
 * equivalent that stays in-tree (engine/render_plan.py,
 * cluster/store.py, controllers/device_player.py); when the toolchain
 * is missing the Python paths run instead.
 *
 * Functions:
 *   build(comp, vals)                -> patch        (render_plan._build)
 *   status_commit(objects, items, rv_start, namespaced, ev_cls)
 *                                    -> (results, evs, last_rv)
 *   filter_stale(evs, rows, written) -> [ev, ...]    (self-echo drop)
 *   cache_apply(cache, evs)          -> None         (informer mirror)
 *   fast_group(...)                  -> (noops, slow_rows)  (drain loop)
 *   confirm_batch(...)               -> (n_ok, releases, fallbacks)
 *
 * Types:
 *   WatchEvent — slot-backed (type, object, rv) event; swapped in for
 *   the Python dataclass by cluster/store.py so status_commit can
 *   allocate events without a Python-level __init__ call per row.
 *   A fourth slot, line, holds the event's NDJSON watch line once a
 *   stream has encoded it (None until then; no part of equality).
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stddef.h>
#include <stdlib.h>

/* Py_T_* member-def names are 3.12+; map to the structmember.h
 * spellings on older CPythons so the extension builds on 3.10/3.11. */
#if PY_VERSION_HEX < 0x030c0000
#include <structmember.h>
#ifndef Py_T_OBJECT_EX
#define Py_T_OBJECT_EX T_OBJECT_EX
#endif
#ifndef Py_T_LONGLONG
#define Py_T_LONGLONG T_LONGLONG
#endif
#endif

static PyObject *s_metadata, *s_namespace, *s_name, *s_resourceVersion,
    *s_status, *s_MODIFIED, *s_DELETED, *s_default, *s_empty, *s_type,
    *s_object, *s_spec, *s_labels, *s_annotations, *s_ownerReferences,
    *s_deletionTimestamp, *s_finalizers;

/* ------------------------------------------------------------ WatchEvent */

typedef struct {
    PyObject_HEAD
    PyObject *type;
    PyObject *object;
    long long rv;
    PyObject *line; /* bytes of the watch line, None until encoded */
} FastEvent;

static PyTypeObject FastEventType; /* fwd */

static PyObject *
fastevent_new(PyTypeObject *tp, PyObject *args, PyObject *kwds)
{
    static char *kwlist[] = {"type", "object", "rv", "line", NULL};
    PyObject *type, *object, *line = Py_None;
    long long rv = 0;
    if (!PyArg_ParseTupleAndKeywords(args, kwds, "OO|LO", kwlist, &type,
                                     &object, &rv, &line))
        return NULL;
    FastEvent *ev = (FastEvent *)tp->tp_alloc(tp, 0);
    if (!ev)
        return NULL;
    Py_INCREF(type);
    ev->type = type;
    Py_INCREF(object);
    ev->object = object;
    ev->rv = rv;
    Py_INCREF(line);
    ev->line = line;
    return (PyObject *)ev;
}

static void
fastevent_dealloc(FastEvent *ev)
{
    Py_XDECREF(ev->type);
    Py_XDECREF(ev->object);
    Py_XDECREF(ev->line);
    Py_TYPE(ev)->tp_free((PyObject *)ev);
}

static PyObject *
fastevent_richcompare(PyObject *a, PyObject *b, int op)
{
    if (op != Py_EQ && op != Py_NE)
        Py_RETURN_NOTIMPLEMENTED;
    if (!PyObject_TypeCheck(a, &FastEventType) ||
        !PyObject_TypeCheck(b, &FastEventType))
        Py_RETURN_NOTIMPLEMENTED;
    FastEvent *x = (FastEvent *)a, *y = (FastEvent *)b;
    int eq = x->rv == y->rv; /* line is a cache, not compared */
    if (eq) {
        eq = PyObject_RichCompareBool(x->type, y->type, Py_EQ);
        if (eq < 0)
            return NULL;
    }
    if (eq) {
        eq = PyObject_RichCompareBool(x->object, y->object, Py_EQ);
        if (eq < 0)
            return NULL;
    }
    if (op == Py_NE)
        eq = !eq;
    if (eq)
        Py_RETURN_TRUE;
    Py_RETURN_FALSE;
}

static PyMemberDef fastevent_members[] = {
    {"type", Py_T_OBJECT_EX, offsetof(FastEvent, type), 0, NULL},
    {"object", Py_T_OBJECT_EX, offsetof(FastEvent, object), 0, NULL},
    {"rv", Py_T_LONGLONG, offsetof(FastEvent, rv), 0, NULL},
    {"line", Py_T_OBJECT_EX, offsetof(FastEvent, line), 0, NULL},
    {NULL},
};

static PyTypeObject FastEventType = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "kwok_fastdrain.WatchEvent",
    .tp_basicsize = sizeof(FastEvent),
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_new = fastevent_new,
    .tp_dealloc = (destructor)fastevent_dealloc,
    .tp_richcompare = fastevent_richcompare,
    .tp_members = fastevent_members,
};

/* ---------------------------------------------------------------- build */

static PyObject *
build_node(PyObject *comp, PyObject *vals)
{
    PyObject *kind = PyTuple_GET_ITEM(comp, 0);
    PyObject *orig = PyTuple_GET_ITEM(comp, 1);
    PyObject *items = PyTuple_GET_ITEM(comp, 2);
    const char *k = PyUnicode_AsUTF8(kind);
    if (!k)
        return NULL;
    switch (k[0]) {
    case 'x': { /* exact token: typed substitution */
        PyObject *v = PyDict_GetItemWithError(vals, orig);
        if (!v) {
            if (!PyErr_Occurred())
                PyErr_SetObject(PyExc_KeyError, orig);
            return NULL;
        }
        Py_INCREF(v);
        return v;
    }
    case 's': { /* string leaf with embedded tokens */
        PyObject *cur = orig;
        Py_INCREF(cur);
        Py_ssize_t n = PyList_GET_SIZE(items);
        for (Py_ssize_t i = 0; i < n; i++) {
            PyObject *tok = PyList_GET_ITEM(items, i);
            PyObject *v = PyDict_GetItemWithError(vals, tok);
            if (!v) {
                Py_DECREF(cur);
                if (!PyErr_Occurred())
                    PyErr_SetObject(PyExc_KeyError, tok);
                return NULL;
            }
            PyObject *vs = PyObject_Str(v);
            if (!vs) {
                Py_DECREF(cur);
                return NULL;
            }
            PyObject *next = PyUnicode_Replace(cur, tok, vs, -1);
            Py_DECREF(vs);
            Py_DECREF(cur);
            if (!next)
                return NULL;
            cur = next;
        }
        return cur;
    }
    case 'd': {
        PyObject *out = PyDict_Copy(orig);
        if (!out)
            return NULL;
        Py_ssize_t n = PyList_GET_SIZE(items);
        for (Py_ssize_t i = 0; i < n; i++) {
            PyObject *pair = PyList_GET_ITEM(items, i);
            PyObject *key = PyTuple_GET_ITEM(pair, 0);
            PyObject *child = PyTuple_GET_ITEM(pair, 1);
            PyObject *v = build_node(child, vals);
            if (!v || PyDict_SetItem(out, key, v) < 0) {
                Py_XDECREF(v);
                Py_DECREF(out);
                return NULL;
            }
            Py_DECREF(v);
        }
        return out;
    }
    case 'l': {
        PyObject *out = PySequence_List(orig);
        if (!out)
            return NULL;
        Py_ssize_t n = PyList_GET_SIZE(items);
        for (Py_ssize_t i = 0; i < n; i++) {
            PyObject *pair = PyList_GET_ITEM(items, i);
            Py_ssize_t idx = PyLong_AsSsize_t(PyTuple_GET_ITEM(pair, 0));
            PyObject *child = PyTuple_GET_ITEM(pair, 1);
            PyObject *v = build_node(child, vals);
            if (!v) {
                Py_DECREF(out);
                return NULL;
            }
            if (PyList_SetItem(out, idx, v) < 0) { /* steals v */
                Py_DECREF(out);
                return NULL;
            }
        }
        return out;
    }
    default:
        PyErr_SetString(PyExc_ValueError, "bad comp node kind");
        return NULL;
    }
}

static PyObject *
py_build(PyObject *self, PyObject *args)
{
    PyObject *comp, *vals;
    if (!PyArg_ParseTuple(args, "OO", &comp, &vals))
        return NULL;
    return build_node(comp, vals);
}

/* -------------------------------------------------------- status_commit */

/* A status item may carry, as a 4th element, the resourceVersion its
 * status was rendered against (the sender's mirror of the object).  A
 * stored object at any other resourceVersion was written by somebody
 * else in between, and replacing its status wholesale would drop what
 * that writer set: the row is refused (result False) and the sender
 * plays it again as a merge patch.  1 = stale, 0 = current or no base
 * given, -1 = error set. */
static int
stale_base(PyObject *item, PyObject *cur)
{
    if (PyTuple_GET_SIZE(item) < 4)
        return 0;
    PyObject *base = PyTuple_GET_ITEM(item, 3);
    if (base == Py_None)
        return 0;
    PyObject *meta = PyDict_GetItemWithError(cur, s_metadata);
    if (!meta || !PyDict_Check(meta))
        return PyErr_Occurred() ? -1 : 0; /* the commit raises KeyError */
    PyObject *rv = PyDict_GetItemWithError(meta, s_resourceVersion);
    if (!rv)
        return PyErr_Occurred() ? -1 : 1;
    if (rv == base)
        return 0;
    int eq = PyObject_RichCompareBool(rv, base, Py_EQ);
    return eq < 0 ? -1 : !eq;
}

static PyObject *
py_status_commit(PyObject *self, PyObject *args)
{
    PyObject *objects, *items, *ev_cls;
    long long rv;
    int namespaced;
    if (!PyArg_ParseTuple(args, "OOLpO", &objects, &items, &rv, &namespaced,
                          &ev_cls))
        return NULL;
    Py_ssize_t n = PyList_GET_SIZE(items);
    PyObject *results = PyList_New(0);
    PyObject *evs = PyList_New(0);
    if (!results || !evs)
        goto fail;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *item = PyList_GET_ITEM(items, i); /* (ns, name, status[, base rv]) */
        PyObject *ns = PyTuple_GET_ITEM(item, 0);
        PyObject *name = PyTuple_GET_ITEM(item, 1);
        PyObject *status = PyTuple_GET_ITEM(item, 2);
        PyObject *keyns;
        if (namespaced)
            keyns = (ns != Py_None && PyObject_IsTrue(ns)) ? ns : s_default;
        else
            keyns = s_empty;
        PyObject *key = PyTuple_Pack(2, keyns, name);
        if (!key)
            goto fail;
        PyObject *cur = PyDict_GetItemWithError(objects, key);
        if (!cur) {
            Py_DECREF(key);
            if (PyErr_Occurred())
                goto fail;
            if (PyList_Append(results, Py_None) < 0)
                goto fail;
            continue;
        }
        {
            int stale = stale_base(item, cur);
            if (stale) {
                Py_DECREF(key);
                if (stale < 0 || PyList_Append(results, Py_False) < 0)
                    goto fail;
                continue;
            }
        }
        PyObject *newobj = PyDict_Copy(cur);
        if (!newobj) {
            Py_DECREF(key);
            goto fail;
        }
        if (PyDict_SetItem(newobj, s_status, status) < 0)
            goto fail_new;
        PyObject *meta = PyDict_GetItemWithError(cur, s_metadata);
        if (!meta) {
            if (!PyErr_Occurred())
                PyErr_SetString(PyExc_KeyError, "metadata");
            goto fail_new;
        }
        PyObject *nm = PyDict_Copy(meta);
        if (!nm)
            goto fail_new;
        rv += 1;
        PyObject *rvs = PyUnicode_FromFormat("%lld", rv);
        if (!rvs || PyDict_SetItem(nm, s_resourceVersion, rvs) < 0) {
            Py_XDECREF(rvs);
            Py_DECREF(nm);
            goto fail_new;
        }
        Py_DECREF(rvs);
        if (PyDict_SetItem(newobj, s_metadata, nm) < 0) {
            Py_DECREF(nm);
            goto fail_new;
        }
        Py_DECREF(nm);
        if (PyDict_SetItem(objects, key, newobj) < 0)
            goto fail_new;
        Py_DECREF(key);
        key = NULL;
        {
            PyObject *ev;
            if (ev_cls == (PyObject *)&FastEventType) {
                /* direct slot alloc: no Python __init__ per row */
                FastEvent *fe = PyObject_New(FastEvent, &FastEventType);
                if (!fe)
                    goto fail_new2;
                Py_INCREF(s_MODIFIED);
                fe->type = s_MODIFIED;
                Py_INCREF(newobj);
                fe->object = newobj;
                fe->rv = rv;
                Py_INCREF(Py_None);
                fe->line = Py_None;
                ev = (PyObject *)fe;
            } else {
                ev = PyObject_CallFunction(ev_cls, "OOL", s_MODIFIED,
                                           newobj, rv);
            }
            if (!ev)
                goto fail_new2;
            if (PyList_Append(evs, ev) < 0) {
                Py_DECREF(ev);
                goto fail_new2;
            }
            Py_DECREF(ev);
        }
        {
            PyObject *res = Py_BuildValue("(LO)", rv, newobj);
            if (!res)
                goto fail_new2;
            if (PyList_Append(results, res) < 0) {
                Py_DECREF(res);
                goto fail_new2;
            }
            Py_DECREF(res);
        }
        Py_DECREF(newobj);
        continue;
    fail_new:
        Py_DECREF(key);
    fail_new2:
        Py_DECREF(newobj);
        goto fail;
    }
    return Py_BuildValue("(NNL)", results, evs, rv);
fail:
    Py_XDECREF(results);
    Py_XDECREF(evs);
    return NULL;
}

/* --------------------------------------------------------- filter_stale */

/* parse a resourceVersion string to int; returns 0 and sets *ok=0 when
 * non-numeric */
static long long
rv_to_ll(PyObject *rvs, int *ok)
{
    *ok = 0;
    if (!rvs || !PyUnicode_Check(rvs))
        return 0;
    const char *sp = PyUnicode_AsUTF8(rvs);
    if (!sp || !*sp)
        return 0;
    char *end = NULL;
    long long v = strtoll(sp, &end, 10);
    if (end && *end == '\0')
        *ok = 1;
    return v;
}

static PyObject *
py_filter_stale(PyObject *self, PyObject *args)
{
    PyObject *evs, *rows, *written;
    if (!PyArg_ParseTuple(args, "OOO", &evs, &rows, &written))
        return NULL;
    Py_ssize_t n = PyList_GET_SIZE(evs);
    PyObject *out = PyList_New(0);
    if (!out)
        return NULL;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *ev = PyList_GET_ITEM(evs, i);
        int keep = 1;
        PyObject *type = PyObject_GetAttr(ev, s_type);
        if (!type)
            goto err;
        int is_mod = PyUnicode_Check(type) &&
                     PyUnicode_Compare(type, s_MODIFIED) == 0;
        Py_DECREF(type);
        if (is_mod) {
            PyObject *obj = PyObject_GetAttr(ev, s_object);
            if (!obj)
                goto err;
            PyObject *meta = PyDict_GetItemWithError(obj, s_metadata);
            if (meta && PyDict_Check(meta)) {
                PyObject *ns = PyDict_GetItemWithError(meta, s_namespace);
                PyObject *name = PyDict_GetItemWithError(meta, s_name);
                if (!ns || ns == Py_None)
                    ns = s_empty;
                if (!name || name == Py_None)
                    name = s_empty;
                PyObject *key = PyTuple_Pack(2, ns, name);
                if (!key) {
                    Py_DECREF(obj);
                    goto err;
                }
                PyObject *row = PyDict_GetItemWithError(rows, key);
                Py_DECREF(key);
                if (row) {
                    Py_ssize_t ridx = PyLong_AsSsize_t(row);
                    PyObject *last =
                        (ridx >= 0 && ridx < PyList_GET_SIZE(written))
                            ? PyList_GET_ITEM(written, ridx)
                            : NULL;
                    if (last && last != Py_None) {
                        PyObject *rvs =
                            PyDict_GetItemWithError(meta, s_resourceVersion);
                        if (rvs && PyUnicode_Check(rvs) &&
                            PyUnicode_Check(last)) {
                            if (PyUnicode_Compare(rvs, last) == 0) {
                                keep = 0;
                            } else {
                                int ok1, ok2;
                                long long a = rv_to_ll(rvs, &ok1);
                                long long b = rv_to_ll(last, &ok2);
                                if (ok1 && ok2 && a <= b)
                                    keep = 0;
                            }
                        }
                    }
                }
            }
            Py_DECREF(obj);
        }
        if (PyErr_Occurred())
            goto err;
        if (keep && PyList_Append(out, ev) < 0)
            goto err;
    }
    return out;
err:
    Py_DECREF(out);
    return NULL;
}

/* ----------------------------------------------------------- fast_group */

/* Per-row drain loop for one (stage, sig) group on the columnar fast
 * path (mirror of the Python loop in
 * controllers/device_player.py::_drain_tick):
 *
 *   fast_group(objects, rows, s_idx, comp, bound, vals_cache,
 *              row_vals_cb, check_noop, has_null, all_top_plain,
 *              top_plain, merge_cb, fast_rows, fast_items)
 *     -> (noop_count, slow_rows)
 *
 * Per row: resolve (or compute via row_vals_cb) the sentinel vals,
 * build the patch, merge it onto the current status (wholesale-replace
 * shortcut when the plan allows; merge_cb = apply_merge_patch
 * otherwise), optionally drop pure no-ops, and append
 * (ns, name, new_status, the mirror's resourceVersion) to fast_items.
 * Rows whose build/merge raises land in slow_rows for the per-row
 * fallback path. */
static PyObject *
py_fast_group(PyObject *self, PyObject *args)
{
    PyObject *objects, *rows, *s_idx, *comp, *bound, *vals_cache,
        *row_vals_cb, *top_plain, *merge_cb, *fast_rows, *fast_items;
    int check_noop, has_null, all_top_plain;
    if (!PyArg_ParseTuple(args, "OOOOOOOiiiOOOO", &objects, &rows, &s_idx,
                          &comp, &bound, &vals_cache, &row_vals_cb,
                          &check_noop, &has_null, &all_top_plain, &top_plain,
                          &merge_cb, &fast_rows, &fast_items))
        return NULL;
    Py_ssize_t n = PyList_GET_SIZE(rows);
    long long noops = 0;
    PyObject *slow_rows = PyList_New(0);
    if (!slow_rows)
        return NULL;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *row_obj = PyList_GET_ITEM(rows, i);
        Py_ssize_t row = PyLong_AsSsize_t(row_obj);
        if (row < 0 && PyErr_Occurred())
            goto err;
        PyObject *obj = PyList_GET_ITEM(objects, row);
        if (obj == Py_None)
            continue;
        PyObject *patch; /* owned */
        if (comp == Py_None) {
            patch = bound; /* tick-static: shared by rows */
            Py_INCREF(patch);
        } else {
            /* vals_cache is row-indexed (caller guarantees length >=
             * capacity; bounds-checked anyway — an IndexError must not
             * become a use-after-free) */
            if (row >= PyList_GET_SIZE(vals_cache)) {
                PyErr_SetString(PyExc_IndexError,
                                "vals_cache shorter than row index");
                goto err;
            }
            PyObject *rowc = PyList_GET_ITEM(vals_cache, row);
            if (rowc == Py_None) {
                rowc = PyDict_New();
                if (!rowc)
                    goto err;
                Py_INCREF(rowc); /* keep ours across the steal */
                if (PyList_SetItem(vals_cache, row, rowc) < 0) {
                    Py_DECREF(rowc);
                    goto err;
                }
                Py_DECREF(rowc); /* the list holds it now */
            }
            PyObject *vals = PyDict_GetItemWithError(rowc, s_idx);
            if (!vals) {
                if (PyErr_Occurred())
                    goto err;
                vals = PyObject_CallFunctionObjArgs(row_vals_cb, obj, NULL);
                if (!vals) {
                    PyErr_Clear();
                    if (PyList_Append(slow_rows, row_obj) < 0)
                        goto err;
                    continue;
                }
                if (PyDict_SetItem(rowc, s_idx, vals) < 0) {
                    Py_DECREF(vals);
                    goto err;
                }
                Py_DECREF(vals); /* rowc keeps it alive */
            }
            patch = build_node(comp, vals);
            if (!patch) {
                PyErr_Clear();
                if (PyList_Append(slow_rows, row_obj) < 0)
                    goto err;
                continue;
            }
        }
        PyObject *cur = PyDict_GetItemWithError(obj, s_status); /* borrowed */
        if (!cur && PyErr_Occurred()) {
            Py_DECREF(patch);
            goto err;
        }
        if (cur == Py_None)
            cur = NULL;
        PyObject *new_status; /* owned */
        if (!cur || (PyDict_Check(cur) && PyDict_GET_SIZE(cur) == 0)) {
            new_status = patch;
            Py_INCREF(new_status);
            if (check_noop && PyDict_Check(patch) &&
                PyDict_GET_SIZE(patch) == 0) {
                noops++;
                Py_DECREF(new_status);
                Py_DECREF(patch);
                continue;
            }
        } else if (!has_null && all_top_plain && PyDict_Check(cur)) {
            int subset = 1;
            Py_ssize_t pos = 0;
            PyObject *k, *v;
            while (PyDict_Next(cur, &pos, &k, &v)) {
                int in = PySet_Contains(top_plain, k);
                if (in < 0) {
                    Py_DECREF(patch);
                    goto err;
                }
                if (!in) {
                    subset = 0;
                    break;
                }
            }
            if (subset) {
                new_status = patch;
                Py_INCREF(new_status);
            } else {
                new_status = PyDict_Copy(cur);
                if (!new_status || PyDict_Update(new_status, patch) < 0) {
                    Py_XDECREF(new_status);
                    Py_DECREF(patch);
                    goto err;
                }
            }
        } else {
            new_status =
                PyObject_CallFunctionObjArgs(merge_cb, cur, patch, NULL);
            if (!new_status) {
                PyErr_Clear();
                Py_DECREF(patch);
                if (PyList_Append(slow_rows, row_obj) < 0)
                    goto err;
                continue;
            }
        }
        Py_DECREF(patch);
        if (check_noop && cur) {
            int same = PyObject_RichCompareBool(new_status, cur, Py_EQ);
            if (same < 0) {
                Py_DECREF(new_status);
                goto err;
            }
            if (same) {
                noops++;
                Py_DECREF(new_status);
                continue;
            }
        }
        PyObject *meta = PyDict_GetItemWithError(obj, s_metadata);
        if (!meta || !PyDict_Check(meta)) {
            Py_DECREF(new_status);
            if (PyErr_Occurred())
                goto err;
            continue;
        }
        PyObject *ns = PyDict_GetItemWithError(meta, s_namespace);
        if (!ns) {
            if (PyErr_Occurred()) {
                Py_DECREF(new_status);
                goto err;
            }
            ns = Py_None;
        }
        PyObject *name = PyDict_GetItemWithError(meta, s_name);
        if (!name || name == Py_None) {
            if (PyErr_Occurred()) {
                Py_DECREF(new_status);
                goto err;
            }
            name = s_empty;
        }
        /* the mirror's resourceVersion: what new_status was merged onto */
        PyObject *base = PyDict_GetItemWithError(meta, s_resourceVersion);
        if (!base) {
            if (PyErr_Occurred()) {
                Py_DECREF(new_status);
                goto err;
            }
            base = Py_None;
        }
        PyObject *item = PyTuple_Pack(4, ns, name, new_status, base);
        Py_DECREF(new_status);
        if (!item)
            goto err;
        if (PyList_Append(fast_items, item) < 0) {
            Py_DECREF(item);
            goto err;
        }
        Py_DECREF(item);
        if (PyList_Append(fast_rows, row_obj) < 0)
            goto err;
    }
    return Py_BuildValue("(LN)", noops, slow_rows);
err:
    Py_DECREF(slow_rows);
    return NULL;
}

/* -------------------------------------------------------- confirm_batch */

/* missing-treated-as-None equality with a pointer shortcut: the store's
 * status commit shares every unchanged subtree, so the common case is
 * pointer-equal */
static int
eq_field(PyObject *a, PyObject *b)
{
    if (!a)
        a = Py_None;
    if (!b)
        b = Py_None;
    if (a == b)
        return 1;
    return PyObject_RichCompareBool(a, b, Py_EQ);
}

/* Post-commit accounting for the columnar drain (mirror of the Python
 * loop after _store_status_batch in device_player._drain_tick):
 *
 *   confirm_batch(results, rows, items, objects, written, cache)
 *     -> (n_ok, releases, fallback_idx, refused_idx)
 *
 * Per result: None -> the object is gone, its (ns, name) key lands in
 * releases; False -> the store refused the row (its object is not at the
 * resourceVersion the status was rendered against), the result index
 * lands in refused_idx for the per-row merge-patch path; (rv, obj) ->
 * record the written resourceVersion, adopt the
 * store's echo into the row mirror when nothing beyond status changed
 * (pointer-first compare on spec/labels/annotations/ownerReferences/
 * deletionTimestamp/finalizers), else report the result index in
 * fallback_idx for a full host re-extract; (rv, None) -> a store across
 * the wire echoes no object: the new mirror is the old one with the
 * status that was sent and the resourceVersion that came back (the store
 * committed only because the mirror was current, so nothing else can
 * differ).  ``cache`` (may be None) is
 * the informer mirror to maintain directly when the store excluded our
 * own watcher from event delivery; entries only move forward in
 * resourceVersion. */
/* the row mirror after a status commit that echoed no object */
static PyObject *
mirror_after(PyObject *old, PyObject *status, PyObject *rv_obj)
{
    PyObject *om = PyDict_GetItemWithError(old, s_metadata);
    if (!om || !PyDict_Check(om)) {
        if (!PyErr_Occurred())
            PyErr_SetString(PyExc_KeyError, "metadata");
        return NULL;
    }
    PyObject *new_obj = PyDict_Copy(old);
    PyObject *nm = PyDict_Copy(om);
    PyObject *rvs = PyObject_Str(rv_obj);
    if (!new_obj || !nm || !rvs ||
        PyDict_SetItem(nm, s_resourceVersion, rvs) < 0 ||
        PyDict_SetItem(new_obj, s_metadata, nm) < 0 ||
        PyDict_SetItem(new_obj, s_status, status) < 0)
        Py_CLEAR(new_obj);
    Py_XDECREF(nm);
    Py_XDECREF(rvs);
    return new_obj;
}

static int
append_index(PyObject *list, Py_ssize_t i)
{
    PyObject *idx = PyLong_FromSsize_t(i);
    if (!idx)
        return -1;
    int rc = PyList_Append(list, idx);
    Py_DECREF(idx);
    return rc;
}

static PyObject *
py_confirm_batch(PyObject *self, PyObject *args)
{
    PyObject *results, *rows, *items, *objects, *written, *cache;
    if (!PyArg_ParseTuple(args, "OOOOOO", &results, &rows, &items, &objects,
                          &written, &cache))
        return NULL;
    if (cache == Py_None)
        cache = NULL;
    Py_ssize_t n = PyList_GET_SIZE(rows);
    long long n_ok = 0;
    PyObject *releases = PyList_New(0);
    PyObject *fallbacks = PyList_New(0);
    PyObject *refused = PyList_New(0);
    PyObject *built = NULL; /* owned: mirror_after()'s, one row at a time */
    if (!releases || !fallbacks || !refused)
        goto err;
    for (Py_ssize_t i = 0; i < n; i++) {
        Py_CLEAR(built);
        PyObject *res = PyList_GET_ITEM(results, i);
        PyObject *row_obj = PyList_GET_ITEM(rows, i);
        if (res == Py_None) {
            PyObject *item = PyList_GET_ITEM(items, i);
            PyObject *ns = PyTuple_GET_ITEM(item, 0);
            int truthy = (ns != Py_None) ? PyObject_IsTrue(ns) : 0;
            if (truthy < 0)
                goto err;
            PyObject *key = PyTuple_Pack(2, truthy ? ns : s_empty,
                                         PyTuple_GET_ITEM(item, 1));
            if (!key)
                goto err;
            if (PyList_Append(releases, key) < 0) {
                Py_DECREF(key);
                goto err;
            }
            Py_DECREF(key);
            continue;
        }
        if (res == Py_False) {
            if (append_index(refused, i) < 0)
                goto err;
            continue;
        }
        PyObject *rv_obj = PyTuple_GET_ITEM(res, 0);
        PyObject *new_obj = PyTuple_GET_ITEM(res, 1);
        n_ok++;
        Py_ssize_t row = PyLong_AsSsize_t(row_obj);
        if (row < 0 && PyErr_Occurred())
            goto err;
        PyObject *old = PyList_GET_ITEM(objects, row);
        if (new_obj == Py_None) {
            if (old == Py_None)
                continue;
            built = mirror_after(
                old, PyTuple_GET_ITEM(PyList_GET_ITEM(items, i), 2), rv_obj);
            if (!built)
                goto err;
            new_obj = built;
        }
        PyObject *nm = PyDict_GetItemWithError(new_obj, s_metadata);
        if (!nm || !PyDict_Check(nm)) {
            if (PyErr_Occurred())
                goto err;
            continue;
        }
        PyObject *rvs = PyDict_GetItemWithError(nm, s_resourceVersion);
        if (!rvs) {
            if (PyErr_Occurred())
                goto err;
            rvs = Py_None;
        }
        /* written is row-indexed (list), like vals_cache */
        Py_INCREF(rvs);
        if (PyList_SetItem(written, row, rvs) < 0) /* steals */
            goto err;
        if (cache) {
            PyObject *ns = PyDict_GetItemWithError(nm, s_namespace);
            if (!ns || ns == Py_None) {
                if (PyErr_Occurred())
                    goto err;
                ns = s_empty;
            }
            PyObject *name = PyDict_GetItemWithError(nm, s_name);
            if (!name || name == Py_None) {
                if (PyErr_Occurred())
                    goto err;
                name = s_empty;
            }
            PyObject *key = PyTuple_Pack(2, ns, name);
            if (!key)
                goto err;
            /* only move forward: an informer-delivered event for a
             * NEWER write must not be clobbered by this older echo.
             * Pointer shortcut: in steady churn the cache entry IS the
             * row mirror we adopted last tick (we wrote both), so one
             * compare replaces the resourceVersion parse. */
            int write = 1;
            PyObject *curc = PyDict_GetItemWithError(cache, key);
            if (!curc && PyErr_Occurred()) {
                Py_DECREF(key);
                goto err;
            }
            if (curc && curc != old) {
                PyObject *cm = PyDict_GetItemWithError(curc, s_metadata);
                if (cm && PyDict_Check(cm)) {
                    PyObject *crv =
                        PyDict_GetItemWithError(cm, s_resourceVersion);
                    int ok = 0;
                    long long cur_rv = rv_to_ll(crv, &ok);
                    long long new_rv = PyLong_AsLongLong(rv_obj);
                    if (new_rv == -1 && PyErr_Occurred())
                        PyErr_Clear();
                    else if (ok && cur_rv > new_rv)
                        write = 0;
                }
                if (PyErr_Occurred()) {
                    Py_DECREF(key);
                    goto err;
                }
            }
            if (write && PyDict_SetItem(cache, key, new_obj) < 0) {
                Py_DECREF(key);
                goto err;
            }
            Py_DECREF(key);
        }
        if (old == Py_None)
            continue;
        PyObject *om = PyDict_GetItemWithError(old, s_metadata);
        if (!om || !PyDict_Check(om)) {
            if (PyErr_Occurred())
                goto err;
            om = NULL;
        }
        int same = eq_field(PyDict_GetItemWithError(old, s_spec),
                            PyDict_GetItemWithError(new_obj, s_spec));
        if (same > 0 && om)
            same = eq_field(PyDict_GetItemWithError(om, s_labels),
                            PyDict_GetItemWithError(nm, s_labels));
        if (same > 0 && om)
            same = eq_field(PyDict_GetItemWithError(om, s_annotations),
                            PyDict_GetItemWithError(nm, s_annotations));
        if (same > 0 && om)
            same = eq_field(PyDict_GetItemWithError(om, s_ownerReferences),
                            PyDict_GetItemWithError(nm, s_ownerReferences));
        if (same > 0 && om)
            same = eq_field(PyDict_GetItemWithError(om, s_deletionTimestamp),
                            PyDict_GetItemWithError(nm, s_deletionTimestamp));
        if (same > 0 && om)
            same = eq_field(PyDict_GetItemWithError(om, s_finalizers),
                            PyDict_GetItemWithError(nm, s_finalizers));
        if (same < 0 || PyErr_Occurred())
            goto err;
        if (same && om) {
            Py_INCREF(new_obj);
            if (PyList_SetItem(objects, row, new_obj) < 0) { /* steals */
                Py_DECREF(new_obj);
                goto err;
            }
        } else if (append_index(fallbacks, i) < 0) {
            goto err;
        }
    }
    Py_XDECREF(built);
    return Py_BuildValue("(LNNN)", n_ok, releases, fallbacks, refused);
err:
    Py_XDECREF(built);
    Py_XDECREF(releases);
    Py_XDECREF(fallbacks);
    Py_XDECREF(refused);
    return NULL;
}

/* ---------------------------------------------------------- cache_apply */

static PyObject *
py_cache_apply(PyObject *self, PyObject *args)
{
    PyObject *cache, *evs;
    if (!PyArg_ParseTuple(args, "OO", &cache, &evs))
        return NULL;
    Py_ssize_t n = PyList_GET_SIZE(evs);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *ev = PyList_GET_ITEM(evs, i);
        PyObject *type = PyObject_GetAttr(ev, s_type);
        if (!type)
            return NULL;
        PyObject *obj = PyObject_GetAttr(ev, s_object);
        if (!obj) {
            Py_DECREF(type);
            return NULL;
        }
        PyObject *meta = PyDict_GetItemWithError(obj, s_metadata);
        if (!meta || !PyDict_Check(meta)) {
            Py_DECREF(type);
            Py_DECREF(obj);
            if (PyErr_Occurred())
                return NULL;
            continue;
        }
        PyObject *ns = PyDict_GetItemWithError(meta, s_namespace);
        PyObject *name = PyDict_GetItemWithError(meta, s_name);
        if (!ns || ns == Py_None)
            ns = s_empty;
        if (!name || name == Py_None)
            name = s_empty;
        PyObject *key = PyTuple_Pack(2, ns, name);
        if (!key) {
            Py_DECREF(type);
            Py_DECREF(obj);
            return NULL;
        }
        int deleted = PyUnicode_Check(type) &&
                      PyUnicode_Compare(type, s_DELETED) == 0;
        int rc;
        if (deleted) {
            rc = PyDict_DelItem(cache, key);
            if (rc < 0 && PyErr_ExceptionMatches(PyExc_KeyError)) {
                PyErr_Clear();
                rc = 0;
            }
        } else {
            rc = PyDict_SetItem(cache, key, obj);
        }
        Py_DECREF(key);
        Py_DECREF(type);
        Py_DECREF(obj);
        if (rc < 0)
            return NULL;
    }
    Py_RETURN_NONE;
}

/* -------------------------------------------------------------- module */

static PyMethodDef Methods[] = {
    {"build", py_build, METH_VARARGS, "build(comp, vals) -> patch"},
    {"status_commit", py_status_commit, METH_VARARGS,
     "status_commit(objects, items, rv_start, namespaced, ev_cls)"},
    {"filter_stale", py_filter_stale, METH_VARARGS,
     "filter_stale(evs, rows, written) -> fresh events"},
    {"cache_apply", py_cache_apply, METH_VARARGS,
     "cache_apply(cache, evs) -> None"},
    {"fast_group", py_fast_group, METH_VARARGS,
     "fast_group(objects, rows, s_idx, comp, bound, vals_cache, "
     "row_vals_cb, check_noop, has_null, all_top_plain, top_plain, "
     "merge_cb, fast_rows, fast_items) -> (noops, slow_rows)"},
    {"confirm_batch", py_confirm_batch, METH_VARARGS,
     "confirm_batch(results, rows, items, objects, written, cache) -> "
     "(n_ok, releases, fallback_idx, refused_idx)"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "kwok_fastdrain", NULL, -1, Methods,
};

PyMODINIT_FUNC
PyInit_kwok_fastdrain(void)
{
    s_metadata = PyUnicode_InternFromString("metadata");
    s_namespace = PyUnicode_InternFromString("namespace");
    s_name = PyUnicode_InternFromString("name");
    s_resourceVersion = PyUnicode_InternFromString("resourceVersion");
    s_status = PyUnicode_InternFromString("status");
    s_MODIFIED = PyUnicode_InternFromString("MODIFIED");
    s_DELETED = PyUnicode_InternFromString("DELETED");
    s_default = PyUnicode_InternFromString("default");
    s_empty = PyUnicode_InternFromString("");
    s_type = PyUnicode_InternFromString("type");
    s_object = PyUnicode_InternFromString("object");
    s_spec = PyUnicode_InternFromString("spec");
    s_labels = PyUnicode_InternFromString("labels");
    s_annotations = PyUnicode_InternFromString("annotations");
    s_ownerReferences = PyUnicode_InternFromString("ownerReferences");
    s_deletionTimestamp = PyUnicode_InternFromString("deletionTimestamp");
    s_finalizers = PyUnicode_InternFromString("finalizers");
    if (PyType_Ready(&FastEventType) < 0)
        return NULL;
    PyObject *m = PyModule_Create(&moduledef);
    if (!m)
        return NULL;
    Py_INCREF(&FastEventType);
    if (PyModule_AddObject(m, "WatchEvent", (PyObject *)&FastEventType) < 0) {
        Py_DECREF(&FastEventType);
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
