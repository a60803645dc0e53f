// Native columnar bulk insert for the sqlite store.
//
// SqliteStore.write prepares each column of a frame once in numpy (an
// int64 or float64 buffer, or one byte buffer with row offsets, plus a
// null mask) and hands the whole frame to fb_sqlite_insert: BEGIN, one
// prepared INSERT, bind + step + reset per row, COMMIT — the work
// CPython's executemany does with a Python object per cell and the
// interpreter lock taken around every step.  ctypes releases the lock
// for the whole call, so the drain and dispatch threads run beside it
// (firebird_tpu/native/sqlite.py).
//
// No sqlite3.h is installed: the few entry points used are declared
// here from sqlite's stable C ABI, and the library is linked against
// the libsqlite3 that Python's own _sqlite3 module loaded, so the
// writer and every reader run one sqlite.
//
// Build: g++ -O3 -std=c++17 -shared -fPIC -pthread sqlitebulk.cpp
//        <the libsqlite3.so.0 Python loaded> -o libsqlitebulk.so

#include <cstdint>
#include <cstdio>

extern "C" {

typedef struct sqlite3 sqlite3;
typedef struct sqlite3_stmt sqlite3_stmt;
typedef int64_t sqlite3_int64;

int sqlite3_libversion_number(void);
int sqlite3_open_v2(const char* filename, sqlite3** db, int flags,
                    const char* vfs);
int sqlite3_close_v2(sqlite3* db);
int sqlite3_busy_timeout(sqlite3* db, int ms);
int sqlite3_exec(sqlite3* db, const char* sql,
                 int (*callback)(void*, int, char**, char**), void* arg,
                 char** errmsg);
int sqlite3_prepare_v2(sqlite3* db, const char* sql, int nbyte,
                       sqlite3_stmt** stmt, const char** tail);
int sqlite3_bind_int64(sqlite3_stmt* stmt, int i, sqlite3_int64 v);
int sqlite3_bind_double(sqlite3_stmt* stmt, int i, double v);
int sqlite3_bind_text(sqlite3_stmt* stmt, int i, const char* v, int n,
                      void (*destructor)(void*));
int sqlite3_bind_blob(sqlite3_stmt* stmt, int i, const void* v, int n,
                      void (*destructor)(void*));
int sqlite3_bind_zeroblob(sqlite3_stmt* stmt, int i, int n);
int sqlite3_bind_null(sqlite3_stmt* stmt, int i);
int sqlite3_step(sqlite3_stmt* stmt);
int sqlite3_reset(sqlite3_stmt* stmt);
int sqlite3_finalize(sqlite3_stmt* stmt);
int sqlite3_get_autocommit(sqlite3* db);
int sqlite3_extended_errcode(sqlite3* db);
const char* sqlite3_errmsg(sqlite3* db);
const char* sqlite3_errstr(int rc);

}  // extern "C"

namespace {

constexpr int SQLITE_OK = 0;
constexpr int SQLITE_DONE = 101;
constexpr int SQLITE_OPEN_READWRITE = 0x00000002;
constexpr int SQLITE_OPEN_NOMUTEX = 0x00008000;
// SQLITE_STATIC: the bound bytes outlive the step (the caller's buffers).
void (*const kStatic)(void*) = nullptr;

// Column kinds (firebird_tpu/native/sqlite.py KINDS).
enum Kind : int32_t { NUL = 0, INT = 1, REAL = 2, TEXT = 3, BLOB = 4 };

}  // namespace

extern "C" {

// One prepared column: ``data`` holds int64 (INT), float64 (REAL; NaN
// binds NULL) or bytes (TEXT/BLOB, row r at offsets[r]..offsets[r+1]);
// ``nulls`` (may be null) marks the rows that bind NULL.
struct fb_col {
  int32_t kind;
  const void* data;
  const int64_t* offsets;
  const uint8_t* nulls;
};

int fb_sqlite_version(void) { return sqlite3_libversion_number(); }

static int fail(sqlite3* db, int rc, char* err, int errlen) {
  // Capture sqlite's message before anything else touches the handle.
  const char* msg = db ? sqlite3_errmsg(db) : sqlite3_errstr(rc);
  int code = db ? sqlite3_extended_errcode(db) : rc;
  std::snprintf(err, static_cast<size_t>(errlen), "%s", msg ? msg : "");
  return code ? code : rc;
}

// Open ``path`` read-write (it must exist: the Python store creates it and
// its schema), in WAL mode with synchronous=NORMAL and a busy timeout.
// Returns 0 and the handle in *out, or sqlite's error code and message.
int fb_sqlite_open(const char* path, int timeout_ms, sqlite3** out,
                   char* err, int errlen) {
  sqlite3* db = nullptr;
  int rc = sqlite3_open_v2(path, &db,
                           SQLITE_OPEN_READWRITE | SQLITE_OPEN_NOMUTEX,
                           nullptr);
  if (rc == SQLITE_OK) rc = sqlite3_busy_timeout(db, timeout_ms);
  if (rc == SQLITE_OK)
    rc = sqlite3_exec(db, "PRAGMA journal_mode=WAL", nullptr, nullptr,
                      nullptr);
  if (rc == SQLITE_OK)
    rc = sqlite3_exec(db, "PRAGMA synchronous=NORMAL", nullptr, nullptr,
                      nullptr);
  if (rc != SQLITE_OK) {
    rc = fail(db, rc, err, errlen);
    sqlite3_close_v2(db);
    *out = nullptr;
    return rc;
  }
  *out = db;
  return SQLITE_OK;
}

int fb_sqlite_close(sqlite3* db) { return sqlite3_close_v2(db); }

// Run ``sql`` (an INSERT of ncol parameters) once per row, all rows in one
// transaction.  On any error the transaction rolls back, and sqlite's
// extended error code is returned with its message in ``err``.
int fb_sqlite_insert(sqlite3* db, const char* sql, const fb_col* cols,
                     int32_t ncol, int64_t nrows, char* err, int errlen) {
  static const char kEmpty[1] = {0};
  int rc = sqlite3_exec(db, "BEGIN", nullptr, nullptr, nullptr);
  if (rc != SQLITE_OK) return fail(db, rc, err, errlen);
  sqlite3_stmt* stmt = nullptr;
  rc = sqlite3_prepare_v2(db, sql, -1, &stmt, nullptr);
  for (int64_t r = 0; rc == SQLITE_OK && r < nrows; ++r) {
    for (int32_t c = 0; rc == SQLITE_OK && c < ncol; ++c) {
      const fb_col& col = cols[c];
      const int i = c + 1;
      if (col.kind == NUL || (col.nulls && col.nulls[r])) {
        rc = sqlite3_bind_null(stmt, i);
      } else if (col.kind == INT) {
        rc = sqlite3_bind_int64(stmt, i,
                                static_cast<const int64_t*>(col.data)[r]);
      } else if (col.kind == REAL) {
        const double v = static_cast<const double*>(col.data)[r];
        rc = v != v ? sqlite3_bind_null(stmt, i)
                    : sqlite3_bind_double(stmt, i, v);
      } else {
        const int64_t lo = col.offsets[r];
        const int n = static_cast<int>(col.offsets[r + 1] - lo);
        const char* p = static_cast<const char*>(col.data) + lo;
        if (col.kind == TEXT)
          rc = sqlite3_bind_text(stmt, i, n ? p : kEmpty, n, kStatic);
        else
          rc = n ? sqlite3_bind_blob(stmt, i, p, n, kStatic)
                 : sqlite3_bind_zeroblob(stmt, i, 0);
      }
    }
    if (rc != SQLITE_OK) break;
    rc = sqlite3_step(stmt);
    if (rc == SQLITE_DONE) rc = sqlite3_reset(stmt);
  }
  if (rc == SQLITE_OK) {
    sqlite3_finalize(stmt);
    stmt = nullptr;
    rc = sqlite3_exec(db, "COMMIT", nullptr, nullptr, nullptr);
  }
  if (rc != SQLITE_OK) {
    rc = fail(db, rc, err, errlen);
    if (stmt) sqlite3_finalize(stmt);
    if (!sqlite3_get_autocommit(db))
      sqlite3_exec(db, "ROLLBACK", nullptr, nullptr, nullptr);
    return rc;
  }
  return SQLITE_OK;
}

}  // extern "C"
