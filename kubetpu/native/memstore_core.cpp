// Native store core — the versioned object map + watch event ring behind
// kubetpu.store.MemStore (layer 0). The reference's storage layer is native
// code (etcd, compiled Go, spoken over gRPC: apiserver/pkg/storage/etcd3);
// this is the framework's equivalent: the hot create/update/get/list/
// events-since paths in C++, exposed through the CPython C API, holding
// opaque PyObject* values (no serialization on the in-process path).
//
// Concurrency contract: the Python wrapper (kubetpu.store.memstore.MemStore)
// serializes every call under its Condition lock — and CPython extension
// calls hold the GIL — so this core is single-writer by construction and
// keeps no locks of its own.
//
// Build: kubetpu/native/__init__.py compiles this with g++ on first use and
// caches the .so; KUBETPU_NO_NATIVE=1 (or a missing compiler) falls back to
// the pure-Python dict implementation with identical semantics (the test
// suite runs the same contract against both).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <cstring>
#include <deque>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

// codec slots in the per-event wire-body cache (must stay aligned with
// kubetpu.api.codec.WIRE_CODEC_IDS and memstore._WIRE_IDS)
constexpr int kNumCodecs = 2;  // 0 json, 1 binary

struct Event {
  int type;  // 0 ADDED, 1 MODIFIED, 2 DELETED
  std::string kind;
  std::string key;
  PyObject* obj;  // owned reference
  long long rv;
  // serialize-once body ring: the event's wire bytes per codec, encoded
  // at most once (events are immutable — writes replace objects — so a
  // cached body can never go stale; it dies with the ring entry)
  PyObject* bodies[kNumCodecs];  // owned references or nullptr
};

// seq is the insertion order (stable across updates) so list() returns the
// same ordering as the pure-Python dict core — informer replace/replay
// order, and therefore cache insertion order and score tie-breaking, must
// not depend on which store backend is active.
struct Entry {
  PyObject* obj;  // owned reference
  long long rv;
  long long seq;
};

struct StoreObject {
  PyObject_HEAD
  long long rv;
  long long compacted_through;
  long long seq_counter;
  size_t history;
  long long body_hits[kNumCodecs];
  long long body_misses[kNumCodecs];
  std::unordered_map<std::string, Entry>* objects;
  // live objects per kind, kept at every insert/erase of `objects` — what
  // count(kind) answers without a walk
  std::unordered_map<std::string, long long>* kind_counts;
  std::deque<Event>* events;
};

std::string map_key(const char* kind, const char* key) {
  std::string k(kind);
  k.push_back('\x1f');  // unit separator — never in identifiers
  k.append(key);
  return k;
}

void push_event(StoreObject* self, int type, const char* kind,
                const char* key, PyObject* obj) {
  if (self->events->size() >= self->history) {
    Event& old = self->events->front();
    self->compacted_through = old.rv;
    Py_DECREF(old.obj);
    for (int c = 0; c < kNumCodecs; ++c) Py_XDECREF(old.bodies[c]);
    self->events->pop_front();
  }
  Py_INCREF(obj);
  self->events->push_back(Event{type, kind, key, obj, self->rv, {}});
}

// ------------------------------------------------- watch-ring walkers

// Ring entries newer than rv for `kind` (nullptr = every kind), oldest
// first, + the new cursor. Pointers stay valid while the caller holds
// the wrapper's store lock (no concurrent push/pop).
long long collect_since(StoreObject* self, const char* kind, long long rv,
                        std::vector<Event*>* out) {
  if (self->events->empty() || self->events->back().rv <= rv) return rv;
  long long cursor = self->events->back().rv;
  for (auto it = self->events->rbegin(); it != self->events->rend(); ++it) {
    if (it->rv <= rv) break;
    if (!kind || it->kind == kind) out->push_back(&*it);
  }
  std::reverse(out->begin(), out->end());
  return cursor;
}

PyObject* event_tuple(const Event* e) {
  return Py_BuildValue("(issOL)", e->type, e->kind.c_str(), e->key.c_str(),
                       e->obj, e->rv);
}

// One event's cached wire body (new reference), encoding through the
// Python callback on first sight. The callback runs under the wrapper's
// store lock and must never re-enter the store (kubetpu.api.codec's
// encoders are pure).
PyObject* event_body(StoreObject* self, Event* e, int cid,
                     PyObject* encoder) {
  if (e->bodies[cid]) {
    self->body_hits[cid] += 1;
    Py_INCREF(e->bodies[cid]);
    return e->bodies[cid];
  }
  PyObject* body = PyObject_CallFunction(encoder, "isOL", e->type,
                                         e->key.c_str(), e->obj, e->rv);
  if (!body) return nullptr;
  if (!PyBytes_Check(body)) {
    Py_DECREF(body);
    PyErr_SetString(PyExc_TypeError,
                    "event body encoder must return bytes");
    return nullptr;
  }
  self->body_misses[cid] += 1;
  Py_INCREF(body);
  e->bodies[cid] = body;
  return body;
}

// ---------------------------------------------------- selector matching
// The list/watch simple-selector subset (kubetpu.api.selectors
// parse_simple_selector terms: (key, equals, value)) evaluated in C —
// the native half of MemStore.list's server-side filtering.

// obj.labels_dict() (absent method = empty labels) — new reference.
PyObject* get_labels(PyObject* obj) {
  PyObject* meth = PyObject_GetAttrString(obj, "labels_dict");
  if (!meth) {
    PyErr_Clear();
    return PyDict_New();
  }
  PyObject* d = PyObject_CallObject(meth, nullptr);
  Py_DECREF(meth);
  return d;  // nullptr propagates the call's error
}

// fieldSelector path → attribute value (api.selectors.object_field's
// exact map) — new reference; Py_None for unknown paths/absent attrs.
PyObject* field_value(PyObject* obj, const char* path) {
  const char* attr = nullptr;
  if (!std::strcmp(path, "metadata.name")) attr = "name";
  else if (!std::strcmp(path, "metadata.namespace")) attr = "namespace";
  else if (!std::strcmp(path, "spec.nodeName")) attr = "node_name";
  else if (!std::strcmp(path, "status.phase")) attr = "phase";
  else if (!std::strcmp(path, "spec.schedulerName")) attr = "scheduler_name";
  if (!attr) Py_RETURN_NONE;
  PyObject* v = PyObject_GetAttrString(obj, attr);
  if (!v) {
    PyErr_Clear();
    Py_RETURN_NONE;
  }
  return v;
}

// one term against the looked-up value: 1 match, 0 no, -1 error
int term_ok(PyObject* got, int eq, PyObject* value) {
  int equal = PyObject_RichCompareBool(got, value, Py_EQ);
  if (equal < 0) return -1;
  return eq ? equal : !equal;
}

// terms are tuples of (key: str, equals: bool, value: str); empty/None
// means unconstrained. 1 match, 0 no match, -1 error.
int matches_selectors(PyObject* obj, PyObject* lterms, PyObject* fterms) {
  if (lterms && lterms != Py_None && PyTuple_GET_SIZE(lterms) > 0) {
    PyObject* labels = get_labels(obj);
    if (!labels) return -1;
    for (Py_ssize_t i = 0; i < PyTuple_GET_SIZE(lterms); ++i) {
      PyObject* term = PyTuple_GET_ITEM(lterms, i);
      PyObject* key = PyTuple_GET_ITEM(term, 0);
      int eq = PyObject_IsTrue(PyTuple_GET_ITEM(term, 1));
      PyObject* value = PyTuple_GET_ITEM(term, 2);
      PyObject* got = PyDict_GetItemWithError(labels, key);  // borrowed
      if (!got) {
        if (PyErr_Occurred()) {
          Py_DECREF(labels);
          return -1;
        }
        got = Py_None;
      }
      int ok = term_ok(got, eq, value);
      if (ok != 1) {
        Py_DECREF(labels);
        return ok;
      }
    }
    Py_DECREF(labels);
  }
  if (fterms && fterms != Py_None && PyTuple_GET_SIZE(fterms) > 0) {
    for (Py_ssize_t i = 0; i < PyTuple_GET_SIZE(fterms); ++i) {
      PyObject* term = PyTuple_GET_ITEM(fterms, i);
      const char* path = PyUnicode_AsUTF8(PyTuple_GET_ITEM(term, 0));
      if (!path) return -1;
      int eq = PyObject_IsTrue(PyTuple_GET_ITEM(term, 1));
      PyObject* value = PyTuple_GET_ITEM(term, 2);
      PyObject* got = field_value(obj, path);
      if (!got) return -1;
      int ok = term_ok(got, eq, value);
      Py_DECREF(got);
      if (ok != 1) return ok;
    }
  }
  return 1;
}

// ---------------------------------------------------------------- methods

PyObject* store_create(StoreObject* self, PyObject* args) {
  const char* kind;
  const char* key;
  PyObject* obj;
  if (!PyArg_ParseTuple(args, "ssO", &kind, &key, &obj)) return nullptr;
  auto mk = map_key(kind, key);
  if (self->objects->count(mk)) {
    PyErr_Format(PyExc_KeyError, "%s/%s already exists", kind, key);
    return nullptr;
  }
  self->rv += 1;
  Py_INCREF(obj);
  (*self->objects)[mk] = {obj, self->rv, ++self->seq_counter};
  (*self->kind_counts)[kind] += 1;
  push_event(self, 0, kind, key, obj);
  return PyLong_FromLongLong(self->rv);
}

PyObject* store_update(StoreObject* self, PyObject* args) {
  const char* kind;
  const char* key;
  PyObject* obj;
  long long expect = -1;
  if (!PyArg_ParseTuple(args, "ssO|L", &kind, &key, &obj, &expect))
    return nullptr;
  auto mk = map_key(kind, key);
  auto it = self->objects->find(mk);
  bool existed = it != self->objects->end();
  if (expect >= 0) {
    long long have = existed ? it->second.rv : -1;
    if (!existed || have != expect) {
      PyErr_Format(PyExc_ValueError, "%s/%s: expected rv %lld, have %lld",
                   kind, key, expect, have);
      return nullptr;
    }
  }
  self->rv += 1;
  Py_INCREF(obj);
  if (existed) {
    Py_DECREF(it->second.obj);
    it->second.obj = obj;
    it->second.rv = self->rv;  // seq unchanged: updates do not reorder
  } else {
    (*self->objects)[mk] = {obj, self->rv, ++self->seq_counter};
    (*self->kind_counts)[kind] += 1;
  }
  push_event(self, existed ? 1 : 0, kind, key, obj);
  return PyLong_FromLongLong(self->rv);
}

PyObject* store_delete(StoreObject* self, PyObject* args) {
  const char* kind;
  const char* key;
  if (!PyArg_ParseTuple(args, "ss", &kind, &key)) return nullptr;
  auto mk = map_key(kind, key);
  auto it = self->objects->find(mk);
  if (it == self->objects->end()) {
    PyErr_Format(PyExc_KeyError, "%s/%s not found", kind, key);
    return nullptr;
  }
  PyObject* old = it->second.obj;
  self->objects->erase(it);
  (*self->kind_counts)[kind] -= 1;
  self->rv += 1;
  push_event(self, 2, kind, key, old);
  Py_DECREF(old);
  return PyLong_FromLongLong(self->rv);
}

PyObject* store_get(StoreObject* self, PyObject* args) {
  const char* kind;
  const char* key;
  if (!PyArg_ParseTuple(args, "ss", &kind, &key)) return nullptr;
  auto it = self->objects->find(map_key(kind, key));
  if (it == self->objects->end()) {
    return Py_BuildValue("(OL)", Py_None, 0LL);
  }
  return Py_BuildValue("(OL)", it->second.obj, it->second.rv);
}

// count(kind) -> live objects of the kind, O(1): a lookup, never a walk.
PyObject* store_count(StoreObject* self, PyObject* args) {
  const char* kind;
  if (!PyArg_ParseTuple(args, "s", &kind)) return nullptr;
  auto it = self->kind_counts->find(kind);
  return PyLong_FromLongLong(it == self->kind_counts->end() ? 0
                                                            : it->second);
}

// list(kind[, label_terms, field_terms]) — selector terms are evaluated
// HERE (the native list filter): per object, no Python bytecode runs.
PyObject* store_list(StoreObject* self, PyObject* args) {
  const char* kind;
  PyObject* lterms = nullptr;
  PyObject* fterms = nullptr;
  if (!PyArg_ParseTuple(args, "s|OO", &kind, &lterms, &fterms))
    return nullptr;
  std::string prefix(kind);
  prefix.push_back('\x1f');
  struct Hit {
    long long seq;
    const std::string* key;
    const Entry* entry;
    bool operator<(const Hit& o) const { return seq < o.seq; }
  };
  std::vector<Hit> hits;
  for (auto& kv : *self->objects) {
    if (kv.first.compare(0, prefix.size(), prefix) != 0) continue;
    hits.push_back(Hit{kv.second.seq, &kv.first, &kv.second});
  }
  std::sort(hits.begin(), hits.end());  // insertion order, like dict
  PyObject* items = PyList_New(0);
  if (!items) return nullptr;
  for (auto& h : hits) {
    int ok = matches_selectors(h.entry->obj, lterms, fterms);
    if (ok < 0) {
      Py_DECREF(items);
      return nullptr;
    }
    if (!ok) continue;
    PyObject* entry = Py_BuildValue(
        "(sO)", h.key->c_str() + prefix.size(), h.entry->obj);
    if (!entry || PyList_Append(items, entry) < 0) {
      Py_XDECREF(entry);
      Py_DECREF(items);
      return nullptr;
    }
    Py_DECREF(entry);
  }
  PyObject* out = Py_BuildValue("(NL)", items, self->rv);
  return out;
}

// list_page(kind[, label_terms, field_terms, limit, after_seq,
// through_seq]) -> (items [(key, obj, rv)], store_rv, next_seq,
// has_more, through_seq) — one bounded page of the seq-ordered list
// walk (the pagination primitive behind MemStore._list_page_locked).
// Seq order is insertion order and updates never reorder, so a page
// walk resumed at next_seq can neither duplicate nor skip an object
// that existed across the whole walk; through_seq caps the walk at a
// seq bound so objects CREATED mid-walk never splice into later pages
// (through_seq <= 0 captures the current max seq and echoes it back
// for the caller's continue token); limit <= 0 means unbounded (the
// full-list form). Selector-filtered candidates still advance
// next_seq, so a filtered walk always makes progress; has_more reports
// whether any in-bound candidate of the kind remains past this page.
PyObject* store_list_page(StoreObject* self, PyObject* args) {
  const char* kind;
  PyObject* lterms = nullptr;
  PyObject* fterms = nullptr;
  long long limit = 0;
  long long after_seq = 0;
  long long through_seq = 0;
  if (!PyArg_ParseTuple(args, "s|OOLLL", &kind, &lterms, &fterms, &limit,
                        &after_seq, &through_seq))
    return nullptr;
  long long bound = through_seq > 0 ? through_seq : self->seq_counter;
  std::string prefix(kind);
  prefix.push_back('\x1f');
  struct Hit {
    long long seq;
    const std::string* key;
    const Entry* entry;
    bool operator<(const Hit& o) const { return seq < o.seq; }
  };
  std::vector<Hit> hits;
  for (auto& kv : *self->objects) {
    if (kv.first.compare(0, prefix.size(), prefix) != 0) continue;
    if (kv.second.seq <= after_seq || kv.second.seq > bound) continue;
    hits.push_back(Hit{kv.second.seq, &kv.first, &kv.second});
  }
  std::sort(hits.begin(), hits.end());
  PyObject* items = PyList_New(0);
  if (!items) return nullptr;
  long long next_seq = after_seq;
  int has_more = 0;
  for (auto& h : hits) {
    if (limit > 0 && PyList_GET_SIZE(items) >= limit) {
      has_more = 1;
      break;
    }
    int ok = matches_selectors(h.entry->obj, lterms, fterms);
    if (ok < 0) {
      Py_DECREF(items);
      return nullptr;
    }
    if (ok) {
      PyObject* entry = Py_BuildValue(
          "(sOL)", h.key->c_str() + prefix.size(), h.entry->obj,
          h.entry->rv);
      if (!entry || PyList_Append(items, entry) < 0) {
        Py_XDECREF(entry);
        Py_DECREF(items);
        return nullptr;
      }
      Py_DECREF(entry);
    }
    next_seq = h.seq;
  }
  return Py_BuildValue("(NLLOL)", items, self->rv, next_seq,
                       has_more ? Py_True : Py_False, bound);
}

// events_since(kind_or_None, rv) -> (list[(type, kind, key, obj, rv)], cursor)
// raises LookupError when rv predates the ring buffer (compacted).
PyObject* store_events_since(StoreObject* self, PyObject* args) {
  PyObject* kind_obj;
  long long rv;
  if (!PyArg_ParseTuple(args, "OL", &kind_obj, &rv)) return nullptr;
  const char* kind =
      kind_obj == Py_None ? nullptr : PyUnicode_AsUTF8(kind_obj);
  if (kind_obj != Py_None && !kind) return nullptr;
  if (rv < self->compacted_through) {
    PyErr_Format(PyExc_LookupError, "rv %lld compacted (through %lld)", rv,
                 self->compacted_through);
    return nullptr;
  }
  std::vector<Event*> hits;
  long long cursor = collect_since(self, kind, rv, &hits);
  PyObject* out = PyList_New(0);
  if (!out) return nullptr;
  for (Event* e : hits) {
    PyObject* entry = event_tuple(e);
    if (!entry || PyList_Append(out, entry) < 0) {
      Py_XDECREF(entry);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(entry);
  }
  return Py_BuildValue("(NL)", out, cursor);
}

// events_since_bulk({kind: rv, …}) -> ({kind: (events, cursor) | None},
// drain_rv) — every cursor drained in ONE call (None marks a compacted
// kind; the wrapper turns it into a CompactedError VALUE).
PyObject* store_events_since_bulk(StoreObject* self, PyObject* args) {
  PyObject* cursors;
  if (!PyArg_ParseTuple(args, "O!", &PyDict_Type, &cursors)) return nullptr;
  PyObject* out = PyDict_New();
  if (!out) return nullptr;
  PyObject* k;
  PyObject* v;
  Py_ssize_t pos = 0;
  while (PyDict_Next(cursors, &pos, &k, &v)) {
    const char* kind = PyUnicode_AsUTF8(k);
    long long rv = PyLong_AsLongLong(v);
    if (!kind || (rv == -1 && PyErr_Occurred())) {
      Py_DECREF(out);
      return nullptr;
    }
    if (rv < self->compacted_through) {
      if (PyDict_SetItem(out, k, Py_None) < 0) {
        Py_DECREF(out);
        return nullptr;
      }
      continue;
    }
    std::vector<Event*> hits;
    long long cursor = collect_since(self, kind, rv, &hits);
    PyObject* evs = PyList_New(0);
    if (!evs) {
      Py_DECREF(out);
      return nullptr;
    }
    for (Event* e : hits) {
      PyObject* entry = event_tuple(e);
      if (!entry || PyList_Append(evs, entry) < 0) {
        Py_XDECREF(entry);
        Py_DECREF(evs);
        Py_DECREF(out);
        return nullptr;
      }
      Py_DECREF(entry);
    }
    PyObject* pair = Py_BuildValue("(NL)", evs, cursor);
    if (!pair || PyDict_SetItem(out, k, pair) < 0) {
      Py_XDECREF(pair);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(pair);
  }
  return Py_BuildValue("(NL)", out, self->rv);
}

// the body-list builder shared by event_bodies_since(+_bulk)
PyObject* bodies_list(StoreObject* self, std::vector<Event*>& hits, int cid,
                      PyObject* encoder) {
  PyObject* out = PyList_New(0);
  if (!out) return nullptr;
  for (Event* e : hits) {
    PyObject* body = event_body(self, e, cid, encoder);
    if (!body || PyList_Append(out, body) < 0) {
      Py_XDECREF(body);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(body);
  }
  return out;
}

// event_bodies_since(kind_or_None, rv, codec_id, encoder) ->
// (list[bytes], cursor): the serialize-once fan-out path — cached wire
// bodies, no Python-side event materialization.
PyObject* store_event_bodies_since(StoreObject* self, PyObject* args) {
  PyObject* kind_obj;
  long long rv;
  int cid;
  PyObject* encoder;
  if (!PyArg_ParseTuple(args, "OLiO", &kind_obj, &rv, &cid, &encoder))
    return nullptr;
  if (cid < 0 || cid >= kNumCodecs) {
    PyErr_Format(PyExc_ValueError, "codec id %d out of range", cid);
    return nullptr;
  }
  const char* kind =
      kind_obj == Py_None ? nullptr : PyUnicode_AsUTF8(kind_obj);
  if (kind_obj != Py_None && !kind) return nullptr;
  if (rv < self->compacted_through) {
    PyErr_Format(PyExc_LookupError, "rv %lld compacted (through %lld)", rv,
                 self->compacted_through);
    return nullptr;
  }
  std::vector<Event*> hits;
  long long cursor = collect_since(self, kind, rv, &hits);
  PyObject* out = bodies_list(self, hits, cid, encoder);
  if (!out) return nullptr;
  return Py_BuildValue("(NL)", out, cursor);
}

// event_bodies_since_bulk({kind: rv}, codec_id, encoder) ->
// ({kind: (list[bytes], cursor) | None}, drain_rv)
PyObject* store_event_bodies_since_bulk(StoreObject* self, PyObject* args) {
  PyObject* cursors;
  int cid;
  PyObject* encoder;
  if (!PyArg_ParseTuple(args, "O!iO", &PyDict_Type, &cursors, &cid,
                        &encoder))
    return nullptr;
  if (cid < 0 || cid >= kNumCodecs) {
    PyErr_Format(PyExc_ValueError, "codec id %d out of range", cid);
    return nullptr;
  }
  PyObject* out = PyDict_New();
  if (!out) return nullptr;
  PyObject* k;
  PyObject* v;
  Py_ssize_t pos = 0;
  while (PyDict_Next(cursors, &pos, &k, &v)) {
    const char* kind = PyUnicode_AsUTF8(k);
    long long rv = PyLong_AsLongLong(v);
    if (!kind || (rv == -1 && PyErr_Occurred())) {
      Py_DECREF(out);
      return nullptr;
    }
    if (rv < self->compacted_through) {
      if (PyDict_SetItem(out, k, Py_None) < 0) {
        Py_DECREF(out);
        return nullptr;
      }
      continue;
    }
    std::vector<Event*> hits;
    long long cursor = collect_since(self, kind, rv, &hits);
    PyObject* bodies = bodies_list(self, hits, cid, encoder);
    if (!bodies) {
      Py_DECREF(out);
      return nullptr;
    }
    PyObject* pair = Py_BuildValue("(NL)", bodies, cursor);
    if (!pair || PyDict_SetItem(out, k, pair) < 0) {
      Py_XDECREF(pair);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(pair);
  }
  return Py_BuildValue("(NL)", out, self->rv);
}

// body_cache_stats() -> {codec_id: (hits, misses)}
// clear_event_bodies() -> None: drop every cached wire body (the ring
// events themselves stay). Binary bodies embed schema-table ids — a
// scheme registration after bodies were cached shifts those ids, so the
// wrapper flushes the ring when the registry generation moves.
PyObject* store_clear_event_bodies(StoreObject* self, PyObject*) {
  for (auto& e : *self->events) {
    for (int c = 0; c < kNumCodecs; ++c) {
      Py_CLEAR(e.bodies[c]);
    }
  }
  Py_RETURN_NONE;
}

PyObject* store_body_cache_stats(StoreObject* self, PyObject*) {
  PyObject* out = PyDict_New();
  if (!out) return nullptr;
  for (int c = 0; c < kNumCodecs; ++c) {
    PyObject* key = PyLong_FromLong(c);
    PyObject* pair =
        Py_BuildValue("(LL)", self->body_hits[c], self->body_misses[c]);
    if (!key || !pair || PyDict_SetItem(out, key, pair) < 0) {
      Py_XDECREF(key);
      Py_XDECREF(pair);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(key);
    Py_DECREF(pair);
  }
  return out;
}

// ------------------------------------------------- durability surface
// dump() -> [(kind, key, obj, rv), ...] in insertion (seq) order, and
// load_snapshot(items, rv): reset to a recovery snapshot — objects with
// their per-object rvs (CAS survives recovery), store revision rv, event
// ring EMPTY with the compaction horizon at rv. Both mirror the Python
// twin exactly (kubetpu.store.memstore._PyCore) — the WAL recovery path
// replays into either core through this same surface.

PyObject* store_dump(StoreObject* self, PyObject*) {
  struct Hit {
    long long seq;
    const std::string* key;
    const Entry* entry;
    bool operator<(const Hit& o) const { return seq < o.seq; }
  };
  std::vector<Hit> hits;
  hits.reserve(self->objects->size());
  for (auto& kv : *self->objects)
    hits.push_back(Hit{kv.second.seq, &kv.first, &kv.second});
  std::sort(hits.begin(), hits.end());
  PyObject* out = PyList_New(0);
  if (!out) return nullptr;
  for (auto& h : hits) {
    size_t sep = h.key->find('\x1f');
    PyObject* entry = Py_BuildValue(
        "(s#s#OL)", h.key->c_str(), (Py_ssize_t)sep,
        h.key->c_str() + sep + 1, (Py_ssize_t)(h.key->size() - sep - 1),
        h.entry->obj, h.entry->rv);
    if (!entry || PyList_Append(out, entry) < 0) {
      Py_XDECREF(entry);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(entry);
  }
  return out;
}

PyObject* store_load_snapshot(StoreObject* self, PyObject* args) {
  PyObject* items;
  long long rv;
  if (!PyArg_ParseTuple(args, "OL", &items, &rv)) return nullptr;
  PyObject* seq = PySequence_Fast(items, "load_snapshot wants a sequence");
  if (!seq) return nullptr;
  for (auto& kv : *self->objects) Py_DECREF(kv.second.obj);
  self->objects->clear();
  self->kind_counts->clear();
  for (auto& e : *self->events) {
    Py_DECREF(e.obj);
    for (int c = 0; c < kNumCodecs; ++c) Py_XDECREF(e.bodies[c]);
  }
  self->events->clear();
  self->seq_counter = 0;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* item = PySequence_Fast_GET_ITEM(seq, i);  // borrowed
    const char* kind;
    const char* key;
    PyObject* obj;
    long long obj_rv;
    if (!PyArg_ParseTuple(item, "ssOL", &kind, &key, &obj, &obj_rv)) {
      Py_DECREF(seq);
      return nullptr;
    }
    Py_INCREF(obj);
    auto mk = map_key(kind, key);
    auto slot = self->objects->find(mk);
    if (slot == self->objects->end()) {
      (*self->kind_counts)[kind] += 1;
    } else {
      Py_DECREF(slot->second.obj);  // repeated key: the later item wins
    }
    (*self->objects)[mk] = {obj, obj_rv, ++self->seq_counter};
  }
  Py_DECREF(seq);
  self->rv = rv;
  self->compacted_through = rv;
  Py_RETURN_NONE;
}

PyObject* store_resource_version(StoreObject* self, PyObject*) {
  return PyLong_FromLongLong(self->rv);
}

PyObject* store_compacted_through(StoreObject* self, PyObject*) {
  return PyLong_FromLongLong(self->compacted_through);
}

// ----------------------------------------------------------------- type

PyObject* store_new(PyTypeObject* type, PyObject* args, PyObject*) {
  long long history = 8192;
  if (!PyArg_ParseTuple(args, "|L", &history)) return nullptr;
  StoreObject* self = (StoreObject*)type->tp_alloc(type, 0);
  if (!self) return nullptr;
  self->rv = 0;
  self->compacted_through = 0;
  self->seq_counter = 0;
  self->history = (size_t)(history > 0 ? history : 1);
  for (int c = 0; c < kNumCodecs; ++c) {
    self->body_hits[c] = 0;
    self->body_misses[c] = 0;
  }
  self->objects = new std::unordered_map<std::string, Entry>();
  self->kind_counts = new std::unordered_map<std::string, long long>();
  self->events = new std::deque<Event>();
  return (PyObject*)self;
}

void store_dealloc(StoreObject* self) {
  for (auto& kv : *self->objects) Py_DECREF(kv.second.obj);
  for (auto& e : *self->events) {
    Py_DECREF(e.obj);
    for (int c = 0; c < kNumCodecs; ++c) Py_XDECREF(e.bodies[c]);
  }
  delete self->objects;
  delete self->kind_counts;
  delete self->events;
  Py_TYPE(self)->tp_free((PyObject*)self);
}

PyMethodDef store_methods[] = {
    {"create", (PyCFunction)store_create, METH_VARARGS, nullptr},
    {"update", (PyCFunction)store_update, METH_VARARGS, nullptr},
    {"delete", (PyCFunction)store_delete, METH_VARARGS, nullptr},
    {"get", (PyCFunction)store_get, METH_VARARGS, nullptr},
    {"count", (PyCFunction)store_count, METH_VARARGS, nullptr},
    {"list", (PyCFunction)store_list, METH_VARARGS, nullptr},
    {"list_page", (PyCFunction)store_list_page, METH_VARARGS, nullptr},
    {"events_since", (PyCFunction)store_events_since, METH_VARARGS, nullptr},
    {"events_since_bulk", (PyCFunction)store_events_since_bulk, METH_VARARGS,
     nullptr},
    {"event_bodies_since", (PyCFunction)store_event_bodies_since,
     METH_VARARGS, nullptr},
    {"event_bodies_since_bulk", (PyCFunction)store_event_bodies_since_bulk,
     METH_VARARGS, nullptr},
    {"clear_event_bodies", (PyCFunction)store_clear_event_bodies,
     METH_NOARGS, nullptr},
    {"body_cache_stats", (PyCFunction)store_body_cache_stats, METH_NOARGS,
     nullptr},
    {"dump", (PyCFunction)store_dump, METH_NOARGS, nullptr},
    {"load_snapshot", (PyCFunction)store_load_snapshot, METH_VARARGS,
     nullptr},
    {"resource_version", (PyCFunction)store_resource_version, METH_NOARGS,
     nullptr},
    {"compacted_through", (PyCFunction)store_compacted_through, METH_NOARGS,
     nullptr},
    {nullptr, nullptr, 0, nullptr},
};

PyTypeObject StoreType = {
    PyVarObject_HEAD_INIT(nullptr, 0)
};

PyModuleDef module_def = {
    PyModuleDef_HEAD_INIT, "_kubetpu_store",
    "native versioned object store core", -1, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit__kubetpu_store(void) {
  StoreType.tp_name = "_kubetpu_store.StoreCore";
  StoreType.tp_basicsize = sizeof(StoreObject);
  StoreType.tp_flags = Py_TPFLAGS_DEFAULT;
  StoreType.tp_new = store_new;
  StoreType.tp_dealloc = (destructor)store_dealloc;
  StoreType.tp_methods = store_methods;
  if (PyType_Ready(&StoreType) < 0) return nullptr;
  PyObject* m = PyModule_Create(&module_def);
  if (!m) return nullptr;
  Py_INCREF(&StoreType);
  if (PyModule_AddObject(m, "StoreCore", (PyObject*)&StoreType) < 0) {
    Py_DECREF(&StoreType);
    Py_DECREF(m);
    return nullptr;
  }
  return m;
}
